"""90th percentile wait from due time to admission (prefill start)."""
import stats


def read(run):
    v = stats.pct([r.prefill_start - r.due for r in stats.measured(run)
                   if r.prefill_start is not None], 90)
    return None if v is None else v * 1e3
