"""Share of its roofline the flash-attention kernels reach: the least time
the chip could take for the causal attention of the traced steps (the
family's count, forward plus twice that for the backward, over the bf16
peak: the kernel is compute-bound at these shapes, its bytes take a tenth
of that time) over the summed device time of the flash forward and
backward kernels in the trace. The kernels are told by their name:
``pl.pallas_call(name="flash_fwd" | "flash_bwd_dq" | "flash_bwd_dkv")`` is
the HLO instruction's own ``%name`` in the trace, so the Mosaic custom
calls (``tpu_custom_call``) whose short name starts with ``flash_``;
another family's kernel with the same result shape is not attention. A
forward pass recomputed by the remat policy is time the kernels took and no
operation the algorithm requires."""
from benchmark import trace_reduce

META = {"name": "flash_attn_roofline", "layer": "kernels", "unit": "%",
        "share_of_peak": True, "better": "higher", "source": "device_trace",
        "moves": "train_tokens_per_s"}
KERNEL, NAME_HEAD = "custom-call/tpu_custom_call", "flash_"


def read(run):
    if run.profile is None or not run.profile.devices or run.peaks is None:
        return None
    c = run.counters
    seconds, events = trace_reduce.seconds_matching(run.profile, KERNEL,
                                                    head=NAME_HEAD)
    steps = len([s for s in run.profile.spans if s[0] == "train_step"])
    if not events or not steps:
        return None
    flops = 3 * run.cell.family.attention_flops_per_token(
        run.cell.config, c["seq_len"]) * c["tokens_per_step"] * steps
    least = flops / (len(run.devices) * run.peaks["bf16_flops_per_s"])
    return 100.0 * least / seconds
