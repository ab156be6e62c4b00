"""Share of the KV positions the window's decode steps read that they
needed: the sum of ``live_tokens`` over the sum of ``kv_read_tokens``
on the ``repro.scheduler.micro_step`` (and ``spec_verify``) spans."""
import spans


def read(run):
    sp = spans.of(run)
    return None if sp is None else spans.kv_read_useful_share(sp)
