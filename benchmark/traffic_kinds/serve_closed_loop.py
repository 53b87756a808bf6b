"""Serving in a closed loop: ``clients`` callers, each sending its next
request when the last token of the previous one has arrived.

One thread (this one) plays every client: it polls the requests in flight
every ``poll_s`` and answers a finished one with that client's next
request. The program's own loop thread (``EngineLoop``) ticks the
scheduler. Latencies are the program's stamps on ``Request``: host clock,
taken when the token has reached the host.

Every seed sends the same set of (prompt length, output length) pairs, the
quantiles of the two clipped log-normals in one evenly mixed cycle, with
other tokens (and the model other weights), and enters the cycle at a place
of its own. Greedy decoding to a fixed length means that which requests
share a tick follows from the sizes and their order alone: the seed's entry
point gives each run other coincidences of prefills and ticks, so that the
spread over seeds, from which the bounds are set, holds what a change to a
tick's or a prefill's length would reshuffle; any stretch of the cycle
carries much the same mix, so the work of a window hardly differs.

The clients start one after another, each when the one before has its first
token, so the ramp too is counted in ticks and not in seconds; the window
opens when the last has sent its first request. Requests in flight when it
closes are drained after it: their tokens inside the window count. Then the
engine is freed and the plain reference runs once over every finished
request (``check_samples`` caps them; the longest is always among them),
prompt and served tokens together. Two things are compared: the gap by
which a served token's logit lies below the reference's best, and, where the
engine hands out the logits it computed (``LogitTap``), those logits
themselves at some seeded places of the vocabulary.
"""
import math
import random
import statistics
import time

import numpy as np

from benchmark import stats


def quantiles_lognormal(spec, n):
    """``n`` lengths at the mid-quantiles of a log-normal with the given
    median and sigma, clipped to [min, max]."""
    nd = statistics.NormalDist()
    out = []
    for i in range(n):
        z = nd.inv_cdf((i + 0.5) / n)
        x = spec["median"] * math.exp(spec["sigma"] * z)
        out.append(int(min(max(round(x), spec["min"]), spec["max"])))
    return out


def _bit_reversed(n):
    """0..n-1 in bit-reversed order (n a power of two): any run of
    neighbours is spread evenly over the whole range."""
    bits = (n - 1).bit_length()
    if n != 1 << bits:
        raise ValueError(f"pool {n} is not a power of two")
    return [int(format(i, f"0{bits}b")[::-1], 2) if bits else 0
            for i in range(n)]


def make_pool(traffic):
    """The cycle of (prompt_len, output_len), the same for every seed
    (``Plan`` enters it where the seed says). Prompt lengths go round in
    bit-reversed order of their rank and output lengths in another such
    order (a stride coprime to the pool apart), so that any stretch of the
    cycle holds much the same mix of long and short."""
    n = traffic["pool"]
    prompts = sorted(quantiles_lognormal(traffic["prompt_len"], n))
    outputs = sorted(quantiles_lognormal(traffic["output_len"], n))
    order = _bit_reversed(n)
    stride = traffic["pairing_stride"]
    if math.gcd(stride, n) != 1:
        raise ValueError(f"pairing_stride {stride} shares a factor with "
                         f"the pool {n}")
    return [(prompts[r], outputs[(r * stride + 3) % n]) for r in order]


class Plan:
    """Request k of a run: the cycle round and round from the place the
    seed draws, prompt tokens from the seed. A seed moves what the tokens
    are and where the cycle is entered, never the set of sizes or their
    cyclic order."""

    def __init__(self, traffic, seed, vocab_size):
        self.pool = make_pool(traffic)
        self.rng = np.random.default_rng(int(seed) % (1 << 63))
        self.vocab_size = vocab_size
        self.at = self.start = int(self.rng.integers(len(self.pool)))

    def next(self):
        n_prompt, n_out = self.pool[self.at % len(self.pool)]
        self.at += 1
        prompt = self.rng.integers(0, self.vocab_size, n_prompt).tolist()
        return prompt, n_out


def summarise(records, t0, t1, vocab_size):
    """End-to-end numbers of the window [t0, t1) from the finished
    requests' stamps. ``records`` holds (request, wanted tokens)."""
    tokens_in, ttfts, gaps = 0, [], []
    attempted = failed = 0
    for req, wanted in records:
        times = req.token_times
        tokens_in += sum(1 for t in times if t0 <= t < t1)
        gaps += [(b - a) * 1e3 for a, b in zip(times, times[1:])
                 if t0 <= b < t1]
        if t0 <= req.submitted < t1:
            attempted += 1
            bad = (req.state != "done" or len(req.tokens) != wanted
                   or any(not 0 <= t < vocab_size for t in req.tokens))
            failed += bad
            if times:
                ttfts.append((times[0] - req.submitted) * 1e3)
    return {"attempted": attempted, "failed": failed,
            "tokens_in_window": tokens_in, "ttft_ms": ttfts,
            "gap_ms": gaps}


def tick_histogram(records, t0, t1):
    """{ms between consecutive token stamps in the window, to 10 ms:
    how often}: the ticks as the clients saw them."""
    stamps = sorted({t for r, _ in records for t in r.token_times
                     if t0 <= t < t1})
    hist = {}
    for a, b in zip(stamps, stamps[1:]):
        k = int(round((b - a) * 1e2) * 10)
        hist[k] = hist.get(k, 0) + 1
    return dict(sorted(hist.items()))


class LogitTap:
    """Keeps what the timed path itself computed for every token it handed
    out: the logits the engine's prefill and decode entries return, at
    ``columns`` of the vocabulary. Read from the stream of engine calls
    alone: a prefill names its prompt and returns the slot it took, and
    every decode tick that carries the slot until the next prefill takes it
    adds that request's next token. An engine that hands out no logits
    leaves the tap empty, and nothing is compared."""

    def __init__(self, engine, columns):
        self.columns = np.asarray(columns)
        self.streams = {}            # prompt key -> [row of token 0, 1, ...]
        self.owner = {}              # slot -> prompt key
        self.dropped = 0
        start, resume = (engine.start_sequence_sampled,
                         engine.resume_sequence_sampled)
        decode = engine.decode_step_sampled

        def prefill(tokens, params):
            slot, logits, tok = start(tokens, params)
            key = self.key(tokens)
            self.owner[slot] = key
            self.streams[key] = [self.row(logits)]
            return slot, logits, tok

        def resumed(tokens, params):
            # a preempted request's tokens are no longer one to a call
            slot, logits, tok = resume(tokens, params)
            self.owner.pop(slot, None)
            self.dropped += 1
            return slot, logits, tok

        def tick(slot_tokens, params_by_slot):
            out = decode(slot_tokens, params_by_slot)
            for slot, (_, logits) in out.items():
                key = self.owner.get(slot)
                if key is not None:
                    self.streams[key].append(self.row(logits))
            return out

        engine.start_sequence_sampled = prefill
        engine.resume_sequence_sampled = resumed
        engine.decode_step_sampled = tick

    @staticmethod
    def key(prompt):
        return (len(prompt),) + tuple(prompt[:16])

    def row(self, logits):
        if logits is None:
            return None
        return np.asarray(logits, np.float32).reshape(-1)[self.columns]

    def of(self, request):
        """[tokens, columns] for a finished request, or None where the
        engine handed out no logits or not one row a token."""
        rows = self.streams.get(self.key(request.prompt))
        if (not rows or len(rows) != len(request.tokens)
                or any(r is None for r in rows)):
            return None
        return np.stack(rows)


def logits_rel_rms(got, want):
    """How far ``got`` lies from ``want`` ([tokens, columns] each), as the
    root mean square of their difference over that of ``want``, each row
    taken about its own mean over the columns (a shift of a whole row moves
    no token)."""
    d = got.astype(np.float64) - want
    d -= d.mean(axis=1, keepdims=True)
    w = want - want.mean(axis=1, keepdims=True)
    return math.sqrt(np.square(d).sum() / np.square(w).sum())


def check_sample(run, program_free, records, traffic, tap):
    """Free the engine, then hold the finished requests (at most
    ``check_samples``, drawn from the seed, the longest always among them)
    against the plain reference."""
    cell = run.cell
    lim = cell.limits
    done = sorted((r for r, _ in records if r.state == "done" and r.tokens),
                  key=lambda r: r.id)
    if not done:
        program_free()
        run.check("served_logit_gap.max", math.inf,
                  lim["served_logit_gap_max"])
        return
    rng = random.Random(run.seed)
    longest = max(done, key=lambda r: len(r.prompt) + len(r.tokens))
    rest = [r for r in done if r is not longest]
    picked = [longest] + rng.sample(
        rest, min(traffic["check_samples"] - 1, len(rest)))
    samples = [(list(r.prompt), list(r.tokens)) for r in picked]
    handed = [tap.of(r) for r in picked]
    program_free()
    # the control: at the same positions, what the reference computes in
    # the precision below the stated one. ``control_also`` names further
    # precisions that a control run reads and judges nothing by.
    controls = [cell.control_precision] + list(cell.control_also) \
        if run.control else []
    judged = cell.control_precision if run.control else "served"
    t0 = time.monotonic()
    ref = cell.family.reference(
        cell.config, "serve", run.seed, samples=samples,
        pads=traffic["reference_pads"], rows=traffic["reference_rows"],
        columns=tap.columns, chosen_by=controls)
    gaps = {k: np.concatenate(v) for k, v in ref["gaps"].items()}
    print(f"[bench] reference over {len(samples)} requests, "
          f"{gaps['served'].size} served tokens, longest "
          f"{len(longest.prompt)}+{len(longest.tokens)}, "
          f"{time.monotonic() - t0:.1f}s", flush=True)
    for name, g in gaps.items():
        print(f"[bench] gap below the reference's best, {name}: mean "
              f"{g.mean():.6g} max {g.max():.6g}, {int((g > 0).sum())} of "
              f"{g.size} tokens differ from the reference's first",
              flush=True)
    run.check("served_logit_gap.max", gaps[judged].max(),
              lim["served_logit_gap_max"])
    run.check("served_logit_gap.mean", gaps[judged].mean(),
              lim["served_logit_gap_mean"])
    # the logits themselves, where the engine handed them out
    want = ref["logits"]["reference"]
    have = [i for i, h in enumerate(handed) if h is not None]
    rms = {}
    if have:
        rms["served"] = logits_rel_rms(
            np.concatenate([handed[i] for i in have]),
            np.concatenate([want[i] for i in have]))
    for name in controls:
        rms[name] = logits_rel_rms(np.concatenate(ref["logits"][name]),
                                   np.concatenate(want))
    print(f"[bench] logits at {len(tap.columns)} places of the vocabulary "
          f"against the reference's, root mean square of the difference "
          f"over the reference's own: "
          + ", ".join(f"{k} {v:.6g}" for k, v in rms.items())
          + f" ({len(have)} of {len(picked)} requests handed out logits, "
          f"{tap.dropped} resumed)", flush=True)
    if judged in rms:
        run.check("served_logits_rel_rms", rms[judged],
                  lim["served_logits_rel_rms"])


def run(run):
    cell, tr = run.cell, run.cell.traffic
    config = cell.config
    program = cell.family.build(config, "serve", run.devices, run.seed)
    run.mark("engine_warm")
    sched, loop = program.scheduler, program.loop
    plan = Plan(tr, run.seed, program.vocab_size)
    clients = tr["clients"]
    tap = LogitTap(program.engine, np.sort(np.random.default_rng(
        [run.seed % (1 << 63), 1]).choice(
            program.vocab_size, tr["logit_columns"], replace=False)))
    inflight = {}                   # client -> (Request, tokens asked for)
    records = []                    # the same pairs, once finished

    def send(client):
        prompt, n_out = plan.next()
        req = sched.submit(prompt, max_new_tokens=n_out,
                           timeout_s=tr["timeout_s"])
        loop.wake()
        inflight[client] = (req, n_out)

    def poll(sending):
        for client, (req, _) in list(inflight.items()):
            if req.finished.is_set():
                records.append(inflight.pop(client))
                if sending:
                    send(client)

    print(f"[bench] warm-up ms per executable: "
          f"{ {k: round(v, 1) for k, v in program.warmup_ms.items()} }",
          flush=True)
    loop.start()
    send(0)
    for client in range(1, clients):
        before = inflight[client - 1][0]
        while not before.token_times and not before.finished.is_set():
            poll(True)
            time.sleep(tr["poll_s"])
        send(client)
    t0 = run.open_window()
    t1 = t0 + run.seconds
    occ0 = (sched.occupancy_sum, sched.steps)
    rec0 = program.recompiles()
    trace_at = t0 + tr["trace_after_s"] if run.trace else None
    trace_until = None
    while time.monotonic() < t1:
        poll(True)
        now = time.monotonic()
        if trace_at is not None and now >= trace_at:
            run.start_trace()
            trace_at, trace_until = None, time.monotonic() + \
                tr["trace_seconds"]
        elif trace_until is not None and now >= trace_until:
            run.stop_trace()
            trace_until = None
        time.sleep(tr["poll_s"])
    occ1 = (sched.occupancy_sum, sched.steps)
    rec1 = program.recompiles()
    if trace_until is not None:
        run.stop_trace()
    run.window = (t0, t1)
    # drain what is in flight: no new request is sent
    deadline = time.monotonic() + tr["drain_timeout_s"]
    while inflight and time.monotonic() < deadline:
        poll(False)
        time.sleep(tr["poll_s"])
    records += inflight.values()             # never finished: failures
    loop.stop()
    run.read_memory_peak()

    s = summarise(records, t0, t1, program.vocab_size)
    run.attempted, run.failed = s["attempted"], s["failed"]
    run.end_to_end["serve_tokens_per_s"] = s["tokens_in_window"] / (t1 - t0)
    if s["ttft_ms"]:
        run.end_to_end["ttft_p50_ms"] = stats.percentile(s["ttft_ms"], 50)
    if s["gap_ms"]:
        run.end_to_end["gap_p90_ms"] = stats.percentile(s["gap_ms"], 90)
        run.counters["gap_p95_ms"] = stats.percentile(s["gap_ms"], 95)
    steps = occ1[1] - occ0[1]
    run.counters.update(
        occupancy=(occ1[0] - occ0[0]) / steps if steps else None,
        recompiles=rec1 - rec0)
    print(f"[bench] cycle entered at {plan.start}; window {t1 - t0:.3f}s, "
          f"{s['attempted']} requests sent, "
          f"{s['tokens_in_window']} tokens, {len(s['ttft_ms'])} ttft and "
          f"{len(s['gap_ms'])} gap samples, {steps} scheduler steps, "
          f"{len(inflight)} unfinished after the drain", flush=True)

    print(f"[bench] ms between token stamps (to 10 ms: count): "
          f"{tick_histogram(records, t0, t1)}", flush=True)
    check_sample(run, program.free, records, tr, tap)
