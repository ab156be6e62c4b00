"""Device time of the prefill programs per 1000 real prompt tokens they
prefilled, over the traced window."""


def read(run):
    if run.trace is None or "prefill" not in run.trace["programs"]:
        return None
    toks = sum(min(r.n_prompt, run.prefill_chunk) for r in run.records
               if r.prefill_start is not None
               and run.t0 <= r.prefill_start <= run.t1)
    if not toks:
        return None
    return run.trace["programs"]["prefill"]["device_s"] * 1e3 / (
        toks / 1e3)
