"""The grouped CADC expert kernel's share of its roofline over the traced
engine steps, prefill and decode calls together: the least time its calls
could take, over the summed duration of its trace events (named after the
jitted wrapper `cadc_gmm_pallas`). A call's least time is its ops on the
rows the program counted routed to each held expert at the bf16 peak, or
its bytes at HBM bandwidth (those rows in and out; the weights reach the
kernel from VMEM, `flops_lfm2.gmm_call`), whichever is longer. Nothing is
reported unless the events number three a MoE layer for every traced
program."""
import flops_lfm2
import peaks
import trace_reduce

KERNEL = "cadc_gmm_pallas"


def read(rec):
    t = rec.get("trace")
    if rec["kind"] != "serve" or not t or not t["steps"] or \
            "routed" not in t["steps"][0]:
        return None
    cfg = rec["config"]
    ns, n = trace_reduce.kernel_ns(t["device"], t["windows"], KERNEL)
    programs = [rows for s in t["steps"] for _, rows in s["routed"]]
    per_program = 3 * (cfg["num_hidden_layers"] - cfg["num_dense_layers"])
    if ns <= 0 or not programs or n != per_program * len(programs):
        return None
    p = peaks.peaks(rec["device"]["kind"])
    least = sum(max(o / p["bf16"], b / p["hbm_bytes_per_s"])
                for rows in programs
                for o, b in flops_lfm2.gmm_calls(cfg, rows))
    return 100.0 * least / (ns * 1e-9)
