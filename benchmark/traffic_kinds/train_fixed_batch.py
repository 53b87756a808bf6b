"""Training at a fixed batch: a fresh seeded batch every step, all of them
on the device before the window opens, the loss fetched every
``fetch_every`` steps as a user's loop does.

Set-up builds one object (the family's compiled step with its state),
drives it through ``check_steps`` first steps on rows that all differ, and
hands that same object to the window. After the window the plain reference
follows the same first steps and each step's loss, the first gradient's
per-leaf norms (from the optimizer's state after one step) and the
parameters' change after the last are compared.
"""
import time

import numpy as np

from benchmark import stats


def make_batches(seed, traffic, vocab_size):
    """``batches`` distinct (tokens, labels) pairs of [1, batch, T] int32:
    labels are the next tokens of the same seeded stream."""
    rng = np.random.default_rng(int(seed) % (1 << 63))
    n, b, t = traffic["batches"], traffic["batch"], traffic["seq_len"]
    stream = rng.integers(0, vocab_size, (n, 1, b, t + 1), dtype=np.int32)
    return [(s[..., :-1].copy(), s[..., 1:].copy()) for s in stream]


def compare(run, program_numbers, reference_numbers):
    """Put each compared number beside its limit."""
    lim = run.cell.limits
    for i, (p, r) in enumerate(zip(program_numbers["losses"],
                                   reference_numbers["losses"])):
        run.check(f"loss_rel_gap.step{i + 1}", abs(p - r) / abs(r),
                  lim["loss_rel_gap"])
    for what in ("first_grad_norms", "change_norms"):
        p, r = program_numbers[what], reference_numbers[what]
        print(f"[bench] {what} program/reference - 1 by leaf: " + ", ".join(
            f"{k} {p[k] / r[k] - 1:+.2e}" for k in sorted(r)), flush=True)
    gap, leaf = stats.worst_leaf_gap(program_numbers["first_grad_norms"],
                                     reference_numbers["first_grad_norms"])
    run.check(f"first_grad_leaf_gap[{leaf}]", gap,
              lim["first_grad_leaf_gap"])
    gap, leaf = stats.worst_leaf_gap(program_numbers["change_norms"],
                                     reference_numbers["change_norms"])
    run.check(f"change_leaf_gap[{leaf}]", gap, lim["change_leaf_gap"])


def run(run):
    import jax

    cell, tr = run.cell, run.cell.traffic
    fam, config = cell.family, cell.config
    n_check, every = tr["check_steps"], tr["fetch_every"]
    batches = make_batches(run.seed, tr, config["vocab_size"])
    first = batches[:n_check]
    tokens_per_step = tr["batch"] * tr["seq_len"]

    if run.control:
        # the control: the reference at the precision below the stated one,
        # put in the program's place. No window: nothing is timed.
        numbers = fam.reference(config, "train", run.seed, batches=first,
                                precision=cell.control_precision)
        ref = fam.reference(config, "train", run.seed, batches=first)
        compare(run, numbers, ref)
        return

    program = fam.build(config, "train", run.devices, run.seed)
    run.mark("state")
    feed = [tuple(jax.device_put(a, run.devices[0]) for a in b)
            for b in batches]
    run.mark("batches")

    # the first steps, through the window's own call and feed
    losses = [program.step(*feed[0])]
    numbers = {"first_grad_norms": program.first_grad_norms()}
    losses += [program.step(*feed[i]) for i in range(1, n_check)]
    numbers["losses"] = [float(x) for x in losses]
    run.mark("first_steps")
    numbers["change_norms"] = program.change_norms()

    # the window
    spans = run.spans
    groups = []                     # (t_begin, t_end, steps) between fetches
    trace_group = tr["trace_group"] if run.trace else None
    t0 = run.open_window()
    done, group_t, group_n, tracing = 0, t0, 0, False
    while time.monotonic() - t0 < run.seconds:
        tok, lab = feed[(n_check + done) % len(feed)]
        if run.trace:
            with spans.span("train_step", step=done):
                losses.append(program.step(tok, lab))
        else:
            losses.append(program.step(tok, lab))
        done += 1
        group_n += 1
        if group_n == every:
            if run.trace:
                with spans.span("loss_fetch"):
                    float(losses[-1])
            else:
                float(losses[-1])
            now = time.monotonic()
            groups.append((group_t, now, group_n))
            if tracing:
                run.stop_trace()
                tracing = False
            elif len(groups) == trace_group:
                run.start_trace()
                tracing = True
            group_t, group_n = time.monotonic(), 0
    jax.block_until_ready(program.params)
    t1 = time.monotonic()
    if tracing:
        run.stop_trace()
    run.window = (t0, t1)
    run.read_memory_peak()

    window_losses = np.asarray([float(x) for x in losses[n_check:]])
    run.attempted = done
    run.failed = int(np.sum(~np.isfinite(window_losses)))
    run.end_to_end["train_tokens_per_s"] = done * tokens_per_step / (t1 - t0)
    run.counters.update(steps=done, tokens_per_step=tokens_per_step,
                        seq_len=tr["seq_len"], batch=tr["batch"],
                        fetch_groups=groups)
    print(f"[bench] window {t1 - t0:.3f}s, {done} steps, first losses "
          f"{numbers['losses']}, last {window_losses[-1]:.4f}", flush=True)

    program.free()
    del feed
    ref = fam.reference(config, "train", run.seed, batches=first)
    compare(run, numbers, ref)
