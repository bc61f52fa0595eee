"""The whole forward's share of the chip's peak over the traced window:
images completed in it times the model's operations per image (every
conv and the classifier, a multiply-add counted as two), over the window
and the peak of the cell's arithmetic (bf16 for fp32, whose dots run as
one bf16 pass; int8 for q8)."""
import flops
import peaks


def read(rec):
    t = rec.get("trace")
    if rec["kind"] != "cnn" or not t or t["window_s"] <= 0:
        return None
    p = peaks.peaks(rec["device"]["kind"])
    peak = p["int8"] if rec["mode"] == "q8" else p["bf16"]
    ops = 2 * flops.resnet18_macs_per_image(rec["config"])
    images = t["batches"] * rec["batch"]
    return 100.0 * images * ops / (t["window_s"] * peak)
