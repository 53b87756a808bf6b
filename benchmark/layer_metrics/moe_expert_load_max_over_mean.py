"""How unevenly a tick loads the experts this chip holds: the tokens on the
fullest held expert of any layer (``expert_load_max`` of the program's
``serve/decode_tick`` records, ``Scheduler._decode``) over the mean tokens
a held expert and layer (``expert_tokens`` over the held experts of all
expert layers, from the configuration), averaged over the window's ticks
that routed anything here. 1 is an even load; the grouped product's time
follows the fullest expert's row tiles once a tick is no longer bound by
the weights. A program that writes no ``expert_load_max`` gives
nothing."""
from benchmark import program_spans

META = {"name": "moe_expert_load_max_over_mean", "layer": "model",
        "unit": "ratio", "better": "lower", "source": "program_counter",
        "moves": "serve_tokens_per_s"}


def read(run):
    ticks = [r["attrs"] for r in program_spans.named(
        run, "serve/decode_tick") or []
        if r.get("attrs", {}).get("expert_tokens")
        and "expert_load_max" in r["attrs"]]
    dims = getattr(run.cell.family, "dims", None)
    if not ticks or dims is None:
        return None
    s = dims(run.cell.config)
    held = s["G"] * (s["L"] - s["Ld"])
    ratios = [t["expert_load_max"] / (t["expert_tokens"] / held)
              for t in ticks]
    return sum(ratios) / len(ratios)
