"""Median host time of one Gateway.completion(run=False) call."""
import stats


def read(run):
    v = stats.pct([r.submit_end - r.submit_start
                   for r in stats.measured(run) if r.rid], 50)
    return None if v is None else v * 1e3
