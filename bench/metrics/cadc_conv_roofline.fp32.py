"""The fp32 fused CADC conv's share of its roofline over the traced
forwards: the least time its calls could take, over the summed duration of
its trace events. A call's least time is its ops at the bf16 peak (its
dots run as one bf16 pass) or its bytes at HBM bandwidth, whichever is
longer. The conv's events are its custom call, named after the jitted
wrapper `_conv_jit`, counted over the whole profiler session, which holds
exactly the traced forwards."""
import flops
import peaks
import trace_reduce

KERNEL = "_conv_jit"


def read(rec):
    t = rec.get("trace")
    if rec["kind"] != "cnn" or rec["mode"] != "fp32" or not t:
        return None
    ns, n = trace_reduce.kernel_ns(t["session"], None, KERNEL)
    calls = flops.resnet18_conv_calls(rec["config"], rec["batch"], 4)
    if ns <= 0 or n != len(calls) * t["batches"]:
        return None
    p = peaks.peaks(rec["device"]["kind"])
    least = sum(max(o / p["bf16"], b / p["hbm_bytes_per_s"])
                for o, b in calls) * t["batches"]
    return 100.0 * least / (ns * 1e-9)
