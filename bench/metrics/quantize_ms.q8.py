"""Device time a q8 forward spends quantising, in ms: the work charged to
the `quantize_act` (per-tensor activation codes) and `quantize_weight`
(ternary weight codes, every forward) scopes of the CADC layers,
batch norm and ReLU fused into them included. Summed over the profiler
session, which holds exactly the traced forwards, over the forwards;
nothing where the program has no such scope or the events differ in
number from what the compiled program gives."""
import scopes

QUANTIZE = {"quantize_act", "quantize_weight"}


def read(rec):
    if rec.get("mode") != "q8":
        return None
    return scopes.ms_per_forward(
        rec, lambda r: bool(QUANTIZE & set(scopes.parts(r["scope"]))))
