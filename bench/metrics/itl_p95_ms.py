"""95th percentile gap between consecutive streamed tokens."""
import stats


def read(run):
    v = stats.pct(stats.itls_s(run), 95)
    return None if v is None else v * 1e3
