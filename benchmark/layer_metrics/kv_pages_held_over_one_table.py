"""What the page groups save: pages x layers the live slots hold, over what
ONE page table for all the layers would make them hold (every layer every
mapped page): ``held_over_one_table`` of the program's ``serve/decode_tick``
records (``PagedKVCache.held_over_one_table``, read when the tick is fed),
averaged over the window's ticks. 1 where a window group bounds nothing
(every rider shorter than the window); with three sliding layers in four
and riders well past the window it tends to a quarter. A program that
writes no ``held_over_one_table`` (one group, or a parent from before it)
gives nothing."""
from benchmark import program_spans

META = {"name": "kv_pages_held_over_one_table", "layer": "decode engine",
        "unit": "ratio", "better": "lower", "source": "program_counter",
        "moves": "serve_tokens_per_s"}


def read(run):
    ratios = [r["attrs"]["held_over_one_table"]
              for r in program_spans.named(run, "serve/decode_tick") or []
              if r.get("attrs", {}).get("held_over_one_table") is not None]
    if not ratios:
        return None
    return sum(ratios) / len(ratios)
