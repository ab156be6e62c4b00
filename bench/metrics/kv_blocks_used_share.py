"""Mean share of the KV pool's blocks in use, over the window's steps."""
import stats


def read(run):
    ticks = stats.in_window(run)
    if not ticks:
        return None
    return 100.0 * sum(k[2] for k in ticks) / (len(ticks) * run.kv_total)
