"""LFM2's share of the chip's bf16 peak over the traced engine steps: the
operations every prefilled prompt and every decoded token needed outside
the experts (mixers' linears, the conv, dense FFNs, routers, causal
attention over its context, the tied head on the rows whose logits are
used), plus the held experts' on the rows the program counted routed
here, over the summed wall time of those steps."""
import flops_lfm2
import peaks


def read(rec):
    t = rec.get("trace")
    if rec["kind"] != "serve" or not t or not t["steps"] or \
            "routed" not in t["steps"][0]:
        return None
    m = rec["config"]
    ops = 0.0
    for s in t["steps"]:
        ops += sum(flops_lfm2.model_ops(m, p, 0, 1) for p in s["prefilled"])
        ops += sum(flops_lfm2.model_ops(m, 1, c, 1) for c in s["decoded"])
        ops += sum(flops_lfm2.expert_ops(m, rows) for _, rows in s["routed"])
    wall = sum(s["b"] - s["a"] for s in t["steps"])
    return 100.0 * ops / (wall * peaks.peaks(rec["device"]["kind"])["bf16"])
