"""The scoring kernel's share of its roofline: the least time the chip
needs for the bytes and operations of every call in the window (from the
R x D shapes, ``bench/roofline.py``) over the kernel's device time."""

from bench import roofline

# The trace names a Pallas kernel by its HLO: the scorer is the custom call
# that returns (f32[R,1,lanes], s32[R,1,1]) cost rows and winners.
KERNEL = r"tpu_custom_call\S* = \(f32\[\d+,1,\d+\]\S* s32\[\d+,1,1\]"


def read(run):
    tr = run.device_trace
    if tr is None:
        return None
    t = tr.kernel_s(KERNEL)
    shapes = run.notes.get("score_shapes") or []
    if t <= 0 or not shapes:
        return None
    pk = run.peaks()
    need = sum(max(roofline.netkv_score_bytes(r, d) / pk["hbm_bytes_per_s"],
                   roofline.netkv_score_flops(r, d) / pk["bf16_flops"])
               for r, d in shapes)
    return 100.0 * need / t
