"""Device time per step of the model's backward pass, the remat
recompute included: the self time of the ops whose name stack holds the
segment ``transpose(jvp(model))``, averaged over chips.  Ops under a COVAP
bucket scope are left out: ``sync_ms`` reads them, and with
``overlap="fused"`` they run inside the backward pass."""
from bench.scopes import BACKWARD, BUCKET_SCOPES, ms_per_step, scope


def keep(segs) -> bool:
    return BACKWARD in segs and not any(scope(s).startswith(BUCKET_SCOPES)
                                        for s in segs)


def read(trace, ctx):
    return ms_per_step(trace, ctx, keep)
