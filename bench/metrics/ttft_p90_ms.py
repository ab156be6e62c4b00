"""90th percentile time to first token, from when each request was due."""
import stats


def read(run):
    v = stats.pct(stats.ttfts_s(run), 90)
    return None if v is None else v * 1e3
