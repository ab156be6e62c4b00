"""How late the load generator submitted: submit time minus due time."""
import stats


def read(run):
    v = stats.pct([r.submit_start - r.due for r in stats.measured(run)
                   if r.rid], 99)
    return None if v is None else v * 1e3
