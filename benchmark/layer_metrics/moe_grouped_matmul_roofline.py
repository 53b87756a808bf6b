"""Share of its roofline the grouped expert product reaches, ticks and
prefills together. The least time is the larger of what its bytes take at
the HBM bandwidth (the weights of the held experts that got a token, and a
row in and out of each product a routed pair) and what its operations take
at the bfloat16 peak of ``benchmark/peaks.json``: the family's count from
``expert_tokens`` and ``experts_hit`` (both summed over layers) of the
program's ``serve/decode_tick`` and ``serve/prefill`` records inside the
traced window. The time is the summed device time of the operations whose
short name starts with ``moe_grouped_matmul``
(``pl.pallas_call(name="moe_grouped_matmul")`` is the HLO instruction's
own ``%name``). A row tile is visited whole, so rows of padding inside a
visited tile are time the kernel took and no work the algorithm requires.
A program with no such kernel or no ``expert_tokens`` gives nothing."""
from benchmark import program_spans, trace_reduce

META = {"name": "moe_grouped_matmul_roofline", "layer": "kernels",
        "unit": "%", "share_of_peak": True, "better": "higher",
        "source": "device_trace", "moves": "serve_tokens_per_s"}
NAME_HEAD = "moe_grouped_matmul"


def read(run):
    if (run.profile is None or not run.profile.devices
            or run.peaks is None or run.trace_window is None):
        return None
    count = getattr(run.cell.family, "grouped_matmul_work", None)
    seconds, events = trace_reduce.seconds_matching(run.profile,
                                                    head=NAME_HEAD)
    calls = [r["attrs"] for name in ("serve/decode_tick", "serve/prefill")
             for r in program_spans.named(
                 run, name, window=run.trace_window) or []
             if "expert_tokens" in r.get("attrs", {})]
    if count is None or not events or not calls:
        return None
    sv = run.cell.config["serving"]
    width = {"bf16": 2, "f32": 4}[sv["engine"]["weight_dtype"]]
    least_s = 0.0
    for c in calls:         # a call's products are bound one way or the other
        nbytes, flops = count(run.cell.config, c["expert_tokens"],
                              c["experts_hit"], weight_bytes=width)
        least_s += max(nbytes / run.peaks["hbm_bytes_per_s"],
                       flops / run.peaks["bf16_flops_per_s"])
    return 100.0 * least_s / seconds
