"""Model FLOPs utilisation of batch inference, in %: forward operations per
image (``counting.py``) times the images completed per second of the traced
window, over the chip's peak."""
from chipbench import counting


def read(rec):
    rate = rec["units_per_s"]
    if not rate:
        return None
    return 100.0 * counting.vit_forward_flops(rec["config"]) * rate / (
        rec["peaks"]["flops_bf16"] * rec["trace"]["n_devices"])
