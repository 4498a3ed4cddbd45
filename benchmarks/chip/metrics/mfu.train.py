"""Model FLOPs utilisation of training, in %: forward and backward
operations per image (3 x forward, no recompute; ``counting.py``) times the
images completed per second of the traced window, over the chip's peak."""
from chipbench import counting


def read(rec):
    rate = rec["units_per_s"]
    if not rate:
        return None
    return 100.0 * counting.vit_train_flops(rec["config"]) * rate / (
        rec["peaks"]["flops_bf16"] * rec["trace"]["n_devices"])
