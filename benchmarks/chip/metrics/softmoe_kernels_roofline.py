"""Share of their roofline that the Soft-MoE Pallas kernels reach, in %.

Sum over the traced window of each Soft-MoE layer pass's roofline time
(``counting.softmoe_fwd``/``softmoe_bwd``: the algorithm's operations and
least bytes, at the chip's peaks) over the summed device time of the
Soft-MoE kernels in the trace. The kernels carry no name yet, so they are
known by their operands: the slot parameters Phi, [d, s], beside the
tokens, [batch, tokens, d]. Another Pallas call is not counted; a
recomputed (rematerialised) forward is time without a count."""
import re

from chipbench import counting
from chipbench.ref_vit import moe_layers

BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def is_softmoe(operands: str, batch: int, d: int, s: int) -> bool:
    shapes = [tuple(int(x) for x in dims.split(",") if x)
              for dims in re.findall(r"\[([0-9,]*)\]", operands)]
    return (d, s) in shapes and any(
        len(t) == 3 and t[0] == batch and t[2] == d for t in shapes)


def read(rec):
    t, cfg = rec["trace"], rec["config"]
    d = cfg["hidden_size"]
    s = cfg["num_experts"] * cfg["slots_per_expert"]
    kernel_s = sum(sec for ops, (sec, _) in t["pallas_by_operands"].items()
                   if is_softmoe(ops, rec["batch"], d, s))
    if not kernel_s:
        return None
    shape = (rec["batch"], cfg["num_patches"], d, s, BYTES[cfg["dtype"]])
    per_layer = sum(counting.roofline_s(getattr(counting, "softmoe_" + p)(
        *shape), rec["peaks"]) for p in rec["softmoe_passes"])
    roof = rec["calls"] * len(moe_layers(cfg)) * per_layer
    return 100.0 * roof / kernel_s
