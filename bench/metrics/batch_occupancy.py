"""Mean share of decode slots running, over the window's engine steps."""
import stats


def read(run):
    ticks = stats.in_window(run)
    if not ticks:
        return None
    return 100.0 * sum(k[1] for k in ticks) / (len(ticks) * run.max_batch)
