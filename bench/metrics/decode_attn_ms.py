"""Device time per decode call of the leaf operations under the decode
program's ``attn`` scope (the KV gather, its upcast, scores, softmax
and weighted sum).  Puts every named scope's time per call in
run.notes, or says there that the decode program's operations carry no
scope (a program without them, or its executable served by a
persistent compile cache that keys programs without their names)."""
import spans


def read(run):
    sp = spans.of(run)
    if sp is None:
        return None
    per = spans.scope_ms_per_decode(sp)
    run.notes["decode_scope_ms"] = per or "no scoped operation"
    return per.get("attn")
