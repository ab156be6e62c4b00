"""Model FLOPs of every token the window processed (prefilled and
generated), over the window times the chips' bf16 peak."""
import stats


def read(run):
    f = stats.window_flops(run)
    if not f:
        return None
    return 100.0 * f / ((run.t1 - run.t0) * run.chips
                        * run.peaks["bf16_flops"])
