"""The q8 fused CADC conv's share of its roofline over the traced
forwards: the least time its calls could take (each call's ops at the
int8 peak or its bytes at HBM bandwidth, whichever is longer), over the
summed duration of its trace events, which carry the jitted wrapper's name
`_conv_q8_jit`. Its calls are counted over the whole profiler session,
which holds exactly the traced forwards."""
import flops
import peaks
import trace_reduce

KERNEL = "_conv_q8_jit"


def read(rec):
    t = rec.get("trace")
    if rec["kind"] != "cnn" or rec["mode"] != "q8" or not t:
        return None
    ns, n = trace_reduce.kernel_ns(t["session"], None, KERNEL)
    calls = flops.resnet18_conv_calls(rec["config"], rec["batch"], 1)
    if ns <= 0 or n != len(calls) * t["batches"]:
        return None
    p = peaks.peaks(rec["device"]["kind"])
    least = sum(max(o / p["int8"], b / p["hbm_bytes_per_s"])
                for o, b in calls) * t["batches"]
    return 100.0 * least / (ns * 1e-9)
