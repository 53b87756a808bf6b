"""Hardware denominators for throughput/MFU reporting.

One table, shared by bench.py, tools/mfu_sweep.py and the TrainMonitor, so
every reported MFU divides by the SAME bf16-peak denominator (the round-5
lesson: the table briefly held v5e's int8 rate and understated every MFU
2x — PEAK_PROBE.json measures 171.3 TF on a dense bf16 matmul, 87% of 197).
"""
from __future__ import annotations

__all__ = ["peak_bf16_flops", "peak_hbm_bytes_per_s", "ridge_intensity",
           "hbm_capacity_bytes", "program_train_flops"]

# device_kind substring -> peak bf16 FLOP/s
PEAK_BF16_FLOPS = {
    "v6e": 918e12, "v6 lite": 918e12, "v5e": 197e12, "v5 lite": 197e12,
    "v5litepod": 197e12, "v5p": 459e12, "v4": 275e12, "v3": 123e12,
    "v2": 45e12,
}

# device_kind substring -> peak HBM bandwidth, bytes/s (published per-chip
# figures; the roofline's other axis — attribution.py divides achieved
# bytes/s by this to place HBM-bound fusions)
PEAK_HBM_BYTES_PER_S = {
    "v6e": 1640e9, "v6 lite": 1640e9, "v5e": 819e9, "v5 lite": 819e9,
    "v5litepod": 819e9, "v5p": 2765e9, "v4": 1228e9, "v3": 900e9,
    "v2": 700e9,
}

# device_kind substring -> on-chip HBM capacity, bytes (published per-chip
# figures; the autotuner's over-HBM pruning budget — a candidate whose
# predicted peak residency exceeds this never runs a probe)
HBM_CAPACITY_BYTES = {
    "v6e": 32e9, "v6 lite": 32e9, "v5e": 16e9, "v5 lite": 16e9,
    "v5litepod": 16e9, "v5p": 95e9, "v4": 32e9, "v3": 32e9,
    "v2": 16e9,
}

# The CPU lane's placeholders: the CPU tests need finite roofline
# arithmetic, and nothing divided by these is a device metric. An
# accelerator whose kind is in no table is an error, never a default.
_CPU_PLACEHOLDER_FLOPS = 1e12
_CPU_PLACEHOLDER_HBM_BPS = 50e9


def _lookup(table, device, what, cpu_value):
    if device is None:
        import jax

        device = jax.devices()[0]
    kind = str(getattr(device, "device_kind", "cpu")).lower()
    for k, v in table.items():
        if k in kind:
            return v
    if getattr(device, "platform", "cpu") == "cpu":
        return cpu_value
    raise ValueError(
        f"no {what} on record for device kind {kind!r} — add the published "
        "figure to paddle_tpu/observability/hw.py")


def peak_bf16_flops(device=None) -> float:
    """Peak *bf16* FLOP/s for a jax device (or the default device)."""
    return _lookup(PEAK_BF16_FLOPS, device, "peak bf16 FLOP/s",
                   _CPU_PLACEHOLDER_FLOPS)


def peak_hbm_bytes_per_s(device=None) -> float:
    """Peak HBM bandwidth (bytes/s) for a jax device — the roofline's
    memory axis, shared by attribution.py the same way the flops table is
    shared by bench/monitor."""
    return _lookup(PEAK_HBM_BYTES_PER_S, device, "peak HBM bandwidth",
                   _CPU_PLACEHOLDER_HBM_BPS)


def hbm_capacity_bytes(device=None):
    """On-chip HBM capacity in bytes; ``None`` on the CPU (host memory is
    not the scarce resource the tuner prunes against)."""
    return _lookup(HBM_CAPACITY_BYTES, device, "HBM capacity", None)


def ridge_intensity(device=None) -> float:
    """The roofline ridge point, flops/byte: above it a kernel is
    compute-bound, below it HBM-bound (v5e: ~240 flops/byte)."""
    return peak_bf16_flops(device) / peak_hbm_bytes_per_s(device)


def program_train_flops(program, batch: int = 1) -> int:
    """Analytic fwd+bwd FLOPs of one step of a built fluid program: 2*MACs
    over conv2d + matmul/mul ops, times 3 for fwd+bwd — the standard
    training estimate. Dynamic (-1) leading dims — data layers built with
    append_batch_size — are substituted with ``batch``."""
    import numpy as np

    def prod(shape):
        return int(np.prod([batch if d in (-1, None) else d for d in shape]))

    block = program.global_block()
    macs = 0
    for op in block.ops:
        if op.type == "conv2d":
            out = block.var(op.output("Output")[0]).shape
            w = block.var(op.input("Filter")[0]).shape
            groups = int(op.attr("groups", 1) or 1)
            # out [N, Cout, H, W]; w [Cout, Cin/g, kh, kw]
            macs += prod(out) * prod(w[1:]) \
                // max(groups, 1) * groups ** 0  # w already holds Cin/g
        elif op.type in ("mul", "matmul"):
            x = block.var(op.input("X")[0]).shape
            y = block.var(op.input("Y")[0]).shape
            macs += prod(x) * int(y[-1])
    return 6 * macs  # 2 FLOPs/MAC x 3 (fwd + bwd)
