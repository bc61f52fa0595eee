"""Device time a forward spends around the fused CADC conv, in ms: all
work under the scope of a layer that calls the conv kernel but the
kernel's own call and the quantisation of its operands. That is the
wrapper's stride-phase pad (`phases`), weight reshape and Cout pad
(`weights`) and output crop (`crop`), and the layout copies and the
phase split's reshapes, which carry the layer's own scope. Summed over
the profiler session, which holds exactly the traced forwards, over the
forwards; nothing where the program has no such scope or the events
differ in number from what the compiled program gives."""
import scopes

KERNELS = ("_conv_jit", "_conv_q8_jit")
QUANTIZE = {"quantize_act", "quantize_weight"}


def read(rec):
    layers = [s for s in scopes.kernel_scopes(rec, KERNELS) if s]
    if not layers:
        return None

    def pick(r):
        s = r["scope"]
        return (r["op"] not in KERNELS
                and not QUANTIZE & set(scopes.parts(s))
                and any(s == l or s.startswith(l + "/") for l in layers))

    return scopes.ms_per_forward(rec, pick)
