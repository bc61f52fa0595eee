"""The fused CADC matmul's share of its roofline over the traced engine
steps, prefill and decode calls together: the least time its calls could
take, over the summed duration of its trace events (named after the
jitted wrapper `cadc_matmul_pallas`). A call's least time is its ops at
the bf16 peak or its bytes at HBM bandwidth, whichever is longer, counted
on the live rows: the real prompt tokens of a prefill, the decoding slots
of a decode. Rows the program pads to its blocks are its own cost."""
import flops
import peaks
import trace_reduce

KERNEL = "cadc_matmul_pallas"


def read(rec):
    t = rec.get("trace")
    if rec["kind"] != "serve" or not t:
        return None
    cfg = rec["config"]
    ns, n = trace_reduce.kernel_ns(t["device"], t["windows"], KERNEL)
    programs = sum(s["n_prefill_calls"] + s["n_decode_calls"]
                   for s in t["steps"])
    per_program = len(flops.decoder_kernel_calls(cfg, 1))
    if ns <= 0 or n != per_program * programs:
        return None
    p = peaks.peaks(rec["device"]["kind"])
    least = 0.0
    for s in t["steps"]:
        rows = [sum(s["prefilled"])] if s["n_prefill_calls"] else []
        rows += [len(s["decoded"])] if s["n_decode_calls"] else []
        for m in rows:
            least += sum(max(o / p["bf16"], b / p["hbm_bytes_per_s"])
                         for o, b in flops.decoder_kernel_calls(cfg, m))
    return 100.0 * least / (ns * 1e-9)
