"""Device time per call of the decode program."""
import stats


def read(run):
    return stats.decode_ms_per_call(run)
