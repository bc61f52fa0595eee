"""Device time a forward spends in batch norm, ReLU and the residual
adds, in ms: the work charged to the forward's `bn*`, `relu` and
`residual` scopes (a fusion goes to the scope of its root, so BN and ReLU
fused into a quantisation's reduction count there, not here). Summed over
the profiler session, which holds exactly the traced forwards, over the
forwards; nothing where the program has no such scope or the events
differ in number from what the compiled program gives."""
import scopes


def bn_act(part: str) -> bool:
    return part.startswith("bn") or part in ("relu", "residual")


def read(rec):
    return scopes.ms_per_forward(
        rec, lambda r: any(bn_act(p) for p in scopes.parts(r["scope"])))
