"""The model's share of the chip's bf16 peak over the traced engine
steps: the operations every prefilled prompt and every decoded token
needed (linears, causal attention over its context, the LM head on the
rows whose logits are used), over the summed wall time of those steps."""
import flops
import peaks


def read(rec):
    t = rec.get("trace")
    if rec["kind"] != "serve" or not t or not t["steps"]:
        return None
    m = rec["config"]
    ops = 0.0
    for s in t["steps"]:
        ops += sum(flops.decoder_model_ops(m, p, 0, 1) for p in s["prefilled"])
        ops += sum(flops.decoder_model_ops(m, 1, c, 1) for c in s["decoded"])
    wall = sum(s["b"] - s["a"] for s in t["steps"])
    return 100.0 * ops / (wall * peaks.peaks(rec["device"]["kind"])["bf16"])
