"""The sampler does only the work its batch asks for (ISSUE 30,
serving/sampling.py): one of three paths — greedy, temperature only,
filtered — is picked inside the executable from the batch's own sampling
parameters. Every token of every path equals the sampler this replaced
(kept here as the oracle: a ``vmap`` of ``where(temp <= 0, argmax,
categorical(masked))``, which computed the sort and the draw for every
row whatever the rows asked for); the host's name for the path is the
branch the device takes; the vocabulary sort lives inside one branch of a
``cond`` and nowhere else; the engine counts its calls by path, stamps
the path on its ``run`` spans, and never compiles for a change of path.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.extend import core as jcore

from paddle_tpu import serving
from paddle_tpu.models import gpt
from paddle_tpu.observability import metrics as om
from paddle_tpu.observability import spans as _spans
from paddle_tpu.serving import sampling as samp

B, W, V = 8, 3, 517
SP = serving.SamplingParams


# ---------------------------------------------------------------------------
# the oracle: the parent's sampler, every row paying for the sort
# ---------------------------------------------------------------------------

def _oracle_token(logits, temp, top_k, top_p, seed, position):
    logits = logits.astype(jnp.float32)
    greedy_tok = jnp.argmax(logits).astype(jnp.int32)
    key = jnp.stack([position.astype(jnp.uint32),
                     seed.astype(jnp.uint32)])
    sampled = jax.random.categorical(
        key, samp._masked_logits(logits, temp, top_k, top_p)
    ).astype(jnp.int32)
    return jnp.where(temp <= 0.0, greedy_tok, sampled)


def _oracle_batch(logits, temps, top_ks, top_ps, seeds, positions):
    return jax.vmap(_oracle_token)(logits, temps, top_ks, top_ps, seeds,
                                   positions)


def _oracle_window(logits, temps, top_ks, top_ps, seeds, positions):
    def per_slot(lg, t, k, p, s, pos):
        return jax.vmap(
            lambda l, q: _oracle_token(l, t, k, p, s, q))(lg, pos)

    return jax.vmap(per_slot)(logits, temps, top_ks, top_ps, seeds,
                              positions)


# ---------------------------------------------------------------------------
# batches: {slot: SamplingParams} over 8 lanes; absent lanes are inactive
# and ride greedy. Each with the path it must take.
# ---------------------------------------------------------------------------

BATCHES = {
    "all_greedy": ({s: serving.GREEDY for s in range(B)}, "greedy"),
    "inactive_lanes_only": ({}, "greedy"),
    "greedy_with_idle_knobs": (
        # a filter on a greedy row filters nothing: still the argmax alone
        {0: SP(top_k=5), 3: SP(top_p=0.5, seed=9), 6: SP(top_k=2,
                                                         top_p=0.3)},
        "greedy"),
    "temperature_all": (
        {s: SP(temperature=0.4 + 0.3 * s, seed=100 + s) for s in range(B)},
        "temperature"),
    "temperature_beside_greedy": (
        {1: SP(temperature=0.8, seed=1), 2: serving.GREEDY,
         5: SP(temperature=1.7, seed=2 ** 31 + 5)},
        "temperature"),
    "top_k_alone": (
        {s: SP(temperature=1.5, top_k=1 + s, seed=7 * s) for s in range(B)},
        "filtered"),
    "top_p_alone": (
        {s: SP(temperature=1.2, top_p=0.15 + 0.1 * s, seed=11 * s)
         for s in range(B)},
        "filtered"),
    "top_k_and_top_p": (
        {s: SP(temperature=2.0, top_k=3 + 2 * s, top_p=0.9 - 0.1 * s,
               seed=13 * s + 1) for s in range(B)},
        "filtered"),
    "one_filtered_rider": (
        # rows of one batch run in lock step: one top-p request makes
        # greedy and temperature-only riders take the filtered path too
        {0: serving.GREEDY, 1: SP(temperature=0.9, seed=3),
         4: SP(temperature=1.1, top_p=0.6, seed=4),
         7: SP(temperature=3.0, seed=5)},
        "filtered"),
    "top_k_over_the_vocabulary": (
        {2: SP(temperature=1.0, top_k=V + 10, seed=21)}, "filtered"),
}


def _inputs(name):
    params, _path = BATCHES[name]
    rng = np.random.RandomState(sum(map(ord, name)))
    logits = (rng.randn(B, W, V) * 3).astype(np.float32)
    positions = rng.randint(0, 2000, size=(B, W)).astype(np.int32)
    return logits, samp.batch_arrays(params, B), positions


_jit = {f: jax.jit(f) for f in (
    samp.sample_token, samp.sample_batch, samp.sample_window,
    _oracle_token, _oracle_batch, _oracle_window)}


@pytest.mark.parametrize("entry", ["sample_batch", "sample_window",
                                   "sample_token"])
@pytest.mark.parametrize("batch", sorted(BATCHES))
def test_tokens_equal_the_parents_sampler(batch, entry):
    logits, sp, positions = _inputs(batch)
    if entry == "sample_window":
        got = _jit[samp.sample_window](logits, *sp, positions)
        want = _jit[_oracle_window](logits, *sp, positions)
    elif entry == "sample_batch":
        got = _jit[samp.sample_batch](logits[:, 0], *sp, positions[:, 0])
        want = _jit[_oracle_batch](logits[:, 0], *sp, positions[:, 0])
    else:
        # the prefill programs' scalar entry: each row on its own knobs
        rows = [(logits[s, 0], *(a[s] for a in sp), positions[s, 0])
                for s in range(B)]
        got = [_jit[samp.sample_token](*r) for r in rows]
        want = [_jit[_oracle_token](*r) for r in rows]
    got, want = np.asarray(got), np.asarray(want)
    assert got.dtype == np.int32
    np.testing.assert_array_equal(got, want)
    if BATCHES[batch][1] == "greedy":
        first = logits if entry == "sample_window" else logits[:, 0]
        np.testing.assert_array_equal(got, first.argmax(-1))


def test_a_filter_changes_tokens_so_the_cases_can_tell():
    """The filtered cases are no accident of the oracle agreeing with a
    plain draw: with the filter taken off, some token differs."""
    logits, sp, positions = _inputs("top_k_alone")
    temps, top_ks, top_ps, seeds = sp
    off = (temps, np.zeros_like(top_ks), np.ones_like(top_ps), seeds)
    a = np.asarray(_jit[samp.sample_window](logits, *sp, positions))
    b = np.asarray(_jit[samp.sample_window](logits, *off, positions))
    assert (a != b).any()


# ---------------------------------------------------------------------------
# the predicate: one function, host and device
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("batch", sorted(BATCHES))
def test_host_path_is_the_branch_the_device_takes(batch, monkeypatch):
    """Each draw planted with a constant of its own, so the tokens of the
    sampling rows say which branch ran."""
    monkeypatch.setattr(
        samp, "_draw_plain", lambda lg, *a: jnp.int32(-1))
    monkeypatch.setattr(
        samp, "_draw_filtered", lambda lg, *a: jnp.int32(-2))
    logits, sp, positions = _inputs(batch)
    temps, top_ks, top_ps, _seeds = sp
    host = samp.path_name(temps, top_ks, top_ps)
    assert host == BATCHES[batch][1]
    assert host == samp.PATHS[int(jax.jit(samp.sampler_path)(
        temps, top_ks, top_ps))]
    # traced anew under the planted draws (jit's cache knows a function
    # by its identity, not by the globals it reads)
    fresh = {f: jax.jit(lambda *a, f=f: getattr(samp, f)(*a))
             for f in ("sample_token", "sample_batch", "sample_window")}
    planted = {"greedy": set(), "temperature": {-1}, "filtered": {-2}}
    for toks in (
            fresh["sample_batch"](logits[:, 0], *sp, positions[:, 0]),
            fresh["sample_window"](logits, *sp, positions)):
        toks = np.asarray(toks)
        assert set(toks[temps > 0].ravel().tolist()) == planted[host]
        assert (toks[temps <= 0] >= 0).all()
    # the scalar entry decides on its own row alone
    for s in range(B):
        tok = int(fresh["sample_token"](
            logits[s, 0], *(a[s] for a in sp), positions[s, 0]))
        row = samp.path_name(temps[s], top_ks[s], top_ps[s])
        assert tok == {"greedy": int(logits[s, 0].argmax()),
                       "temperature": -1, "filtered": -2}[row]


# ---------------------------------------------------------------------------
# the structure: the sort is inside a cond's branch and nowhere else
# ---------------------------------------------------------------------------

def _sub_jaxprs(eqn):
    for v in eqn.params.values():
        for x in (v if isinstance(v, (tuple, list)) else (v,)):
            if isinstance(x, jcore.ClosedJaxpr):
                yield x.jaxpr
            elif isinstance(x, jcore.Jaxpr):
                yield x


def _primitives(jaxpr, into_cond=True):
    """Names of the primitives of ``jaxpr`` and everything it calls;
    with ``into_cond`` false the branches of a ``cond`` are left out."""
    names = []
    for eqn in jaxpr.eqns:
        names.append(eqn.primitive.name)
        if eqn.primitive.name == "cond" and not into_cond:
            continue
        for sub in _sub_jaxprs(eqn):
            names += _primitives(sub, into_cond)
    return names


def _conds(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "cond":
            yield eqn
        for sub in _sub_jaxprs(eqn):
            yield from _conds(sub)


@pytest.mark.parametrize("entry", ["sample_batch", "sample_window",
                                   "sample_token"])
def test_the_sort_is_inside_one_branch_of_a_cond(entry):
    logits, sp, positions = _inputs("one_filtered_rider")
    if entry == "sample_window":
        args = (logits, *sp, positions)
    elif entry == "sample_batch":
        args = (logits[:, 0], *sp, positions[:, 0])
    else:
        args = (logits[0, 0], *(a[0] for a in sp), positions[0, 0])
    jaxpr = jax.make_jaxpr(getattr(samp, entry))(*args).jaxpr
    dear = {"sort", "exp", "cumsum", "div", "threefry2x32", "log",
            "random_bits"}
    outside = set(_primitives(jaxpr, into_cond=False))
    assert not outside & dear, outside & dear
    (cond,) = _conds(jaxpr)
    greedy, temperature, filtered = (
        set(_primitives(b.jaxpr)) for b in cond.params["branches"])
    assert not greedy & dear and "argmax" not in greedy, greedy
    assert "sort" not in temperature and "cumsum" not in temperature
    assert {"div", "argmax"} <= temperature       # the draw is there
    assert {"sort", "exp", "cumsum", "div", "argmax"} <= filtered
    # and the oracle is what the issue says the parent was: a sort that
    # every call pays, under no cond at all
    parent = jax.make_jaxpr(_oracle_batch)(
        logits[:, 0], *sp, positions[:, 0]).jaxpr
    assert "sort" in _primitives(parent, into_cond=False)
    assert not list(_conds(parent))


# ---------------------------------------------------------------------------
# the engine: counter, span attribute, and no compile for a change of path
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def paged_eng():
    cfg = gpt.GPT_TINY.scaled(num_layers=2, max_seq_len=64)
    params = gpt.init_params(jax.random.PRNGKey(0), cfg)
    eng = serving.DecodeEngine(params, cfg, serving.EngineConfig(
        max_batch=4, max_seq=32, prefill_buckets=(8, 16),
        page_size=8, verify_window=3))
    eng.warmup()
    return eng


def _counts():
    snap = om.default_registry().snapshot()
    series = snap.get("paddle_serve_sampler_path_total", {}).get(
        "series", [])
    return {tuple(s["labels"]): s["value"] for s in series}


def _moved(before):
    after = _counts()
    return {k: v - before.get(k, 0) for k, v in after.items()
            if v != before.get(k, 0)}


def _recompiles():
    snap = om.default_registry().snapshot()
    return sum(s["value"] for s in
               snap.get("paddle_recompiles_total", {}).get("series", []))


def _samplers(name):
    return [s["attrs"]["sampler"] for s in _spans.default_tracer().spans()
            if s["name"] == name]


def test_engine_counts_and_stamps_the_path_and_never_compiles(paged_eng):
    eng = paged_eng
    _spans.default_tracer().clear()
    compiles, recompiles = eng.compiles, _recompiles()
    before = _counts()
    # two greedy requests: greedy prefills, greedy ticks
    s0, _l, t0 = eng.start_sequence_sampled([1, 2, 3, 4, 5], serving.GREEDY)
    s1, _l, t1 = eng.start_sequence_sampled([6, 7, 8], serving.GREEDY)
    out = eng.decode_step_sampled({s0: t0, s1: t1}, None)
    out = eng.decode_step_sampled({s: out[s][0] for s in out},
                                  {s0: serving.GREEDY})
    assert _moved(before) == {("greedy", "prefill"): 2,
                              ("greedy", "decode"): 2}
    assert _samplers("prefill/run") == ["greedy", "greedy"]
    assert _samplers("decode/run") == ["greedy", "greedy"]
    # a temperature-only request joins, then a top-p one: the ticks they
    # ride say so, and the greedy riders' tokens are the argmax as before
    warm = SP(temperature=0.9, seed=5)
    s2, _l, t2 = eng.start_sequence_sampled([9, 10, 11, 12], warm)
    toks = {s: out[s][0] for s in out}
    toks[s2] = t2
    out = eng.decode_step_sampled(toks, {s2: warm})
    assert all(out[s][0] == int(np.argmax(out[s][1])) for s in (s0, s1))
    nucleus = SP(temperature=0.9, top_p=0.7, seed=6)
    s3, _l, t3 = eng.start_sequence_sampled([13, 14], nucleus)
    toks = {s: out[s][0] for s in out}
    toks[s3] = t3
    out = eng.decode_step_sampled(toks, {s2: warm, s3: nucleus})
    assert all(out[s][0] == int(np.argmax(out[s][1])) for s in (s0, s1))
    # the sampled riders leave: the next tick is greedy again
    eng.free_sequence(s2)
    eng.free_sequence(s3)
    eng.decode_step_sampled({s0: out[s0][0], s1: out[s1][0]}, None)
    assert _samplers("prefill/run") == ["greedy", "greedy", "temperature",
                                        "filtered"]
    assert _samplers("decode/run") == ["greedy", "greedy", "temperature",
                                       "filtered", "greedy"]
    # the verify window's program counts too (it opens no span)
    eng.verify_step({s0: [1, 2, 3]}, {s0: SP(temperature=1.0, top_k=2)})
    eng.verify_step({s0: [1, 2, 3]}, None)
    eng.free_sequence(s0)
    eng.free_sequence(s1)
    assert _moved(before) == {
        ("greedy", "prefill"): 2, ("temperature", "prefill"): 1,
        ("filtered", "prefill"): 1, ("greedy", "decode"): 3,
        ("temperature", "decode"): 1, ("filtered", "decode"): 1,
        ("filtered", "verify"): 1, ("greedy", "verify"): 1}
    # one decode executable, one a rung, one verify window: as warmed
    assert eng.compiles == compiles
    assert _recompiles() == recompiles
    assert eng.steady_state_recompiles == 0
