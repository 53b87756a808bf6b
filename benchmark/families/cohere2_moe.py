"""The Cohere2-MoE family (``model_type: cohere2_moe``, Command A+): a
parallel attention + experts block under one LayerNorm, sliding-window and
global layers mixed by ``layer_types``, grouped-query heads, sigmoid-routed
experts with averaged shared experts, a tied head; as ONE chip's share of an
expert-parallel group.

Two halves that share nothing but the seeded weights (as ``kimi_k2.py``):

* ``build`` hands the weights to the program under test
  (``paddle_tpu.models.cohere2_moe`` through ``DecodeEngine`` -> ``Scheduler``
  -> ``EngineLoop``; serving only) and returns the object the timed window
  drives;
* ``reference`` is the plain model: ``jax.numpy`` in float32 under
  ``default_matmul_precision("highest")``, full causal attention a head at a
  time with the band written as a mask, the source's interleaved rotary
  pairs, the experts a loop over the held ones with every token through each,
  the shared experts one by one, no kernel, no cache. It imports nothing of
  the program and draws its weights from the seed **a layer at a time**.

The layer (``d`` hidden, ``H`` query heads over ``KVH`` key/value heads of
``hd``, ``eps`` from the file): ``u = LN(h)`` (mean taken out, a gain, no
bias), ``h += Attn(u) + FFN(u)``; final LN, the head the embedding
transposed, times ``logit_scale``. ``q = u W_q``, ``k = u W_k``, ``v = u
W_v``, query head ``i`` on key/value head ``i // (H / KVH)``, scale ``hd ^
-0.5``. A ``sliding_attention`` layer rotates q and k over the whole head in
interleaved pairs (``rope_theta``) and query ``i`` sees keys ``i -
sliding_window < j <= i``; a ``full_attention`` layer has no position and sees
every ``j <= i``. ``s = sigmoid(u W_r)`` over ALL published experts in
float32, the ``num_experts_per_tok`` largest chosen, weights ``s`` at the
chosen over their sum; ``FFN(u) = sum over chosen experts THIS CHIP HOLDS of
w_k E_k(u) + (1 / S) sum_j S_j(u)``, every expert ``W_down(silu(W_gate u) *
W_up u)`` of width ``intermediate_size``.

**The share.** The configuration holds ``num_experts`` experts from
``first_expert`` on, of ``published.num_experts`` the router scores; that
partial sum is what goes on to the next layer, in the program and here alike:
nothing stands in for the other chips or their exchange. ``vocab_size`` rows
of the published table are held, from row 0.
"""
import functools
import gc
import math

import jax
import jax.numpy as jnp
import numpy as np

MODES = ("serve",)
# the configuration keys that are widths: ``reduced`` may name none of them
# (the router's published width is ``published.num_experts``: ``num_experts``
# at the top level counts the experts HELD, which is the chip's share)
WIDTH_KEYS = ("hidden_size", "num_attention_heads", "num_key_value_heads",
              "head_dim", "intermediate_size", "num_experts_per_tok",
              "num_shared_experts", "sliding_window")
# samples the reference takes through a layer in one call: one, where a
# sample is up to 17k tokens (its float32 q, k, v alone are 1.3 GB)
BLOCKS = (1, 1)
SLIDING, FULL = "sliding_attention", "full_attention"


# ---------------------------------------------------------------------------
# sizes and counts (from the configuration file's published keys)
# ---------------------------------------------------------------------------

def dims(config):
    kinds = list(config["layer_types"])
    if len(kinds) != int(config["num_hidden_layers"]):
        raise ValueError("layer_types does not name num_hidden_layers layers")
    return {"L": int(config["num_hidden_layers"]),
            "Ld": int(config.get("first_k_dense_replace", 0)),
            "kinds": kinds,
            "D": int(config["hidden_size"]),
            "H": int(config["num_attention_heads"]),
            "KVH": int(config["num_key_value_heads"]),
            "hd": int(config["head_dim"]),
            "F": int(config["intermediate_size"]),
            "S": int(config["num_shared_experts"]),
            # the router's width is the published count, whatever is held
            "E": int(config["published"]["num_experts"]),
            "G": int(config["num_experts"]),
            "first": int(config.get("first_expert", 0)),
            "k": int(config["num_experts_per_tok"]),
            "V": int(config["vocab_size"]),
            "W": int(config["sliding_window"]),
            "eps": float(config["layer_norm_eps"]),
            "theta": float(config["rope_theta"]),
            "logit_scale": float(config.get("logit_scale", 1))}


def layer_kinds(config):
    return dims(config)["kinds"]


def leaf_shapes(config, kind=None):
    """One layer's leaves in the published names; matrices ``[in, out]``,
    the shared and the held experts' stacked ``[n, in, out]``. Every layer
    has the same leaves, whatever its kind."""
    s = dims(config)
    D, F = s["D"], s["F"]
    return {"input_layernorm": (D,), "q_proj": (D, s["H"] * s["hd"]),
            "k_proj": (D, s["KVH"] * s["hd"]),
            "v_proj": (D, s["KVH"] * s["hd"]),
            "o_proj": (s["H"] * s["hd"], D), "gate": (D, s["E"]),
            "shared_gate_proj": (s["S"], D, F),
            "shared_up_proj": (s["S"], D, F),
            "shared_down_proj": (s["S"], F, D),
            "experts_gate_proj": (s["G"], D, F),
            "experts_up_proj": (s["G"], D, F),
            "experts_down_proj": (s["G"], F, D)}


TOP_SHAPES = {"embed_tokens": ("V", "D"), "norm": ("D",)}
GAINS = ("input_layernorm", "norm")
OUT_PROJECTIONS = ("o_proj", "shared_down_proj", "experts_down_proj")
# what stays float32 whatever the weights' format: gains, and the router
F32_LEAVES = GAINS + ("gate",)


def _count(shapes, names=None):
    return sum(int(np.prod(v)) for k, v in shapes.items()
               if names is None or k in names)


def expert_params(config):
    """Parameters of ONE expert, routed or shared (gate, up and down)."""
    s = dims(config)
    return 3 * s["D"] * s["F"]


def dense_params_per_step(config):
    """(matrix parameters every tick multiplies whatever it routes: all
    attention projections, the shared experts, the tied head's rows;
    float32 router parameters): the held experts are counted by how many a
    tick hits, the embedding by the rows it reads (the head reads them
    all)."""
    s = dims(config)
    shapes = leaf_shapes(config)
    held = s["V"] * s["D"] + s["L"] * _count(shapes, (
        "q_proj", "k_proj", "v_proj", "o_proj", "shared_gate_proj",
        "shared_up_proj", "shared_down_proj"))
    return held, s["L"] * _count(shapes, ("gate",))


def param_count(config):
    s = dims(config)
    return s["V"] * s["D"] + s["D"] + s["L"] * _count(leaf_shapes(config))


def kv_bytes_per_row(config, cache_bytes=2):
    """What a token leaves in a layer's cache: keys and values of every
    key/value head."""
    s = dims(config)
    return 2 * s["KVH"] * s["hd"] * cache_bytes


def layers_of(config):
    """{"full": layers without a window, "window": sliding layers}."""
    kinds = layer_kinds(config)
    return {"full": kinds.count(FULL), "window": kinds.count(SLIDING)}


def kv_bytes_per_decode_step(config, rows_full, rows_window, cache_bytes=2):
    """Bytes of keys and values a tick has to read: the rows its riders
    have live in each page group (a window group's inside the window), a
    layer of the group each."""
    n = layers_of(config)
    return kv_bytes_per_row(config, cache_bytes) * (
        int(rows_full) * n["full"] + int(rows_window) * n["window"])


def bytes_per_swa_moe_decode_step(config, experts_hit, rows_full,
                                  rows_window, riders, weight_bytes=2,
                                  cache_bytes=2):
    """Least bytes of one decode tick: every non-expert matrix once (the
    router's in float32), the held experts that got a token (``experts_hit``,
    summed over layers) once each, the riders' embedding rows, and the keys
    and values its riders have live in each page group."""
    s = dims(config)
    held, router = dense_params_per_step(config)
    return (held * weight_bytes + router * 4
            + int(experts_hit) * expert_params(config) * weight_bytes
            + int(riders) * s["D"] * weight_bytes
            + kv_bytes_per_decode_step(config, rows_full, rows_window,
                                       cache_bytes))


def grouped_matmul_work(config, expert_tokens, experts_hit, weight_bytes=2,
                        act_bytes=2):
    """(bytes, FLOPs) the grouped expert products have to move and do for
    ``expert_tokens`` (token, choice) pairs on ``experts_hit`` held experts
    (both summed over layers): the hit experts' weights once, a pair's row
    in and out of each of the two products (``D`` in, ``2 F`` out; ``F`` in,
    ``D`` out), and ``2 x 3 D F`` operations a pair."""
    s = dims(config)
    rows = int(expert_tokens) * (2 * s["D"] + 3 * s["F"]) * act_bytes
    return (int(experts_hit) * expert_params(config) * weight_bytes + rows,
            2 * int(expert_tokens) * expert_params(config))


def band_attention_flops(config, prompt_len):
    """Operations of the attention proper (scores and weighted sums) of a
    prompt of ``prompt_len`` tokens, all layers: 4 ``hd`` a (query, key)
    pair and query head, over the pairs inside each layer's causal band
    alone (a full layer: ``j <= i``; a sliding one: ``i - window < j <=
    i``). No padding of a rung and no block on the band's edge is counted
    whole."""
    s = dims(config)
    n = int(prompt_len)
    causal = n * (n + 1) // 2
    w = min(n, s["W"])
    band = w * (w + 1) // 2 + (n - w) * s["W"]
    k = layers_of(config)
    return 4 * s["hd"] * s["H"] * (k["full"] * causal + k["window"] * band)


# ---------------------------------------------------------------------------
# seeded weights: drawn leaf by leaf, keyed by (seed, layer, leaf name)
# ---------------------------------------------------------------------------

def _key(seed):
    seed = int(seed) % (1 << 62)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


_LEAF_IDS = {name: i for i, name in enumerate((
    "embed_tokens", "experts_down_proj", "experts_gate_proj",
    "experts_up_proj", "gate", "input_layernorm", "k_proj", "norm", "o_proj",
    "q_proj", "shared_down_proj", "shared_gate_proj", "shared_up_proj",
    "v_proj"))}


def _draw_leaf(key, layer, name, shape, s):
    """One float32 leaf (``s``: ``dims``); ``key`` and ``layer`` may be
    traced. Projections N(0, 0.02); out-projections (``o_proj`` and every
    ``down``) scaled by ``1 / sqrt(2 L)`` (a parallel block adds both halves
    to the residual at once, two additions a layer as in a sequential one);
    gains ``1 + N(0, 0.02)``. The router has no bias to draw. **The tied
    table is drawn N(0, 0.002)**: it is embedding and head at once, so at
    N(0, 0.02) the token just fed reads its own logit ``|e|^2 / rms(h)``
    2.8 standard deviations above the rest's, greedy decoding repeats one
    token from its first steps on (700 served tokens, 1 distinct), a
    request's served tokens are then ONE routing decision a layer, and a
    near-tie in it that bfloat16 resolves the other way moves them all
    (PERF.md section 6, PR 41: one request of 8 at 0.25, its run at 0.072
    where the others read 0.02); at a tenth of that width the boost is 0.3
    and the streams wander."""
    k = jax.random.fold_in(jax.random.fold_in(key, layer), _LEAF_IDS[name])
    std = 0.02
    z = jax.random.normal(k, shape, jnp.float32)
    if name in GAINS:
        return 1.0 + std * z
    if name == "embed_tokens":
        return z * (std / 10)
    if name in OUT_PROJECTIONS:
        return z * (std / math.sqrt(2 * s["L"]))
    return z * std


def layer_weights(key, config, i):
    s = dims(config)
    return {name: _draw_leaf(key, i, name, shape, s)
            for name, shape in leaf_shapes(config).items()}


def top_weights(key, config):
    s = dims(config)
    return {name: _draw_leaf(key, s["L"], name,
                             tuple(s[d] for d in shape), s)
            for name, shape in TOP_SHAPES.items()}


# leaf of the program's stored tree -> leaf here
_PROGRAM_LAYERS = {"norm": "input_layernorm", "w_q": "q_proj",
                   "w_k": "k_proj", "w_v": "v_proj", "w_o": "o_proj",
                   "router": "gate", "shared_gate": "shared_gate_proj",
                   "shared_up": "shared_up_proj",
                   "shared_down": "shared_down_proj",
                   "w_down": "experts_down_proj"}


def program_weights(seed, config, dtype):
    """The same draws in the program's stored tree
    (``models/cohere2_moe.py:leaf_shapes``): every leaf stacked over the
    layers, each routed expert's gate and up side by side. A stacked leaf
    is filled a layer at a time into one donated buffer, so no float32 copy
    of more than one layer's leaf is ever held."""
    key = _key(seed)
    s = dims(config)
    shapes = leaf_shapes(config)

    def stacked(names):
        """``names``: the leaf here, or several joined on the last axis."""
        held = jnp.float32 if names[0] in F32_LEAVES else dtype

        def one(key, i):
            return jnp.concatenate(
                [_draw_leaf(key, i, n, shapes[n], s) for n in names],
                axis=-1).astype(held)

        shape = jax.eval_shape(one, key, jnp.int32(0)).shape
        fill = jax.jit(lambda buf, key, at, i: buf.at[at].set(one(key, i)),
                       donate_argnums=0)
        buf = jnp.zeros((s["L"],) + shape, held)
        for i in range(s["L"]):
            buf = fill(buf, key, jnp.int32(i), jnp.int32(i))
        return buf

    top = jax.jit(lambda key: top_weights(key, config))(key)
    return {
        "embed": top["embed_tokens"].astype(dtype),
        "final_norm": top["norm"],
        "layers": {**{p: stacked((n,)) for p, n in _PROGRAM_LAYERS.items()},
                   "w_gate_up": stacked(("experts_gate_proj",
                                         "experts_up_proj"))}}


# ---------------------------------------------------------------------------
# the program under test
# ---------------------------------------------------------------------------

class ServeProgram:
    """``DecodeEngine`` -> ``warmup`` -> ``Scheduler`` -> ``EngineLoop``,
    driven in process: the entry points the other serving cells use."""

    def __init__(self, config, devices, seed):
        from paddle_tpu import serving
        from paddle_tpu.models.cohere2_moe import Cohere2MoeConfig
        from paddle_tpu.serving.server import EngineLoop

        sv = config["serving"]
        engine_kw = dict(sv["engine"])
        if "prefill_buckets" in engine_kw:
            engine_kw["prefill_buckets"] = tuple(engine_kw["prefill_buckets"])
        s = dims(config)
        self.cfg = Cohere2MoeConfig(
            vocab_size=s["V"], hidden_size=s["D"], intermediate_size=s["F"],
            num_hidden_layers=s["L"], layer_types=tuple(s["kinds"]),
            num_attention_heads=s["H"], num_key_value_heads=s["KVH"],
            head_dim=s["hd"], sliding_window=s["W"],
            num_experts_published=s["E"], experts_held=s["G"],
            first_expert=s["first"], num_experts_per_tok=s["k"],
            num_shared_experts=s["S"], layer_norm_eps=s["eps"],
            rope_theta=s["theta"], logit_scale=s["logit_scale"],
            dtype=jnp.dtype(sv["compute_dtype"]))
        held = {"bf16": jnp.bfloat16, "f32": jnp.float32}[
            engine_kw["weight_dtype"]]
        with jax.default_device(devices[0]):
            params = program_weights(seed, config, held)
            self.engine = serving.DecodeEngine(
                params, self.cfg, serving.EngineConfig(**engine_kw))
            del params
            self.engine.drop_reference_params()
            self.warmup_ms = self.engine.warmup()
        self.scheduler = serving.Scheduler(
            self.engine, serving.SchedulerConfig(**sv["scheduler"]))
        self.loop = EngineLoop(self.scheduler)
        self.vocab_size = self.cfg.vocab_size

    def recompiles(self):
        from paddle_tpu.observability import metrics as om

        snap = om.default_registry().snapshot()
        total = sum(s["value"] for s in snap.get(
            "paddle_recompiles_total", {}).get("series", []))
        return total + self.engine.steady_state_recompiles

    def free(self):
        """Let go of weights, caches and executables, whoever still holds
        the engine object."""
        self.loop.stop()
        eng = self.engine
        eng.qparams = None
        eng.cache.set_arrays((None,) * len(eng.cache.arrays()))
        eng._exec.clear()
        self.engine = self.scheduler = self.loop = None
        _free_device_memory()


def build(config, mode, devices, seed):
    if mode == "serve":
        return ServeProgram(config, devices, seed)
    raise ValueError(f"mode {mode!r}: expected one of {MODES} (the experts "
                     "have no backward pass: this family is not trained)")


def _free_device_memory():
    gc.collect()
    jax.clear_caches()


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------

def round_weights(w, precision):
    """Every matrix of ``w`` as a weight-only format would hold it
    (``bf16w``; ``int8w``: 8 bits, one scale per 256 values, the engine
    quantiser's granularity); gains and the router stay float32, as the
    program holds them. Activations and arithmetic stay float32."""
    def bf16(x):
        return x.astype(jnp.bfloat16).astype(jnp.float32)

    def int8(x):
        flat = x.reshape(-1)
        pad = (-flat.shape[0]) % 256
        rows = jnp.pad(flat, (0, pad)).reshape(-1, 256)
        scale = jnp.max(jnp.abs(rows), axis=1, keepdims=True) / 127.0
        q = jnp.round(rows / jnp.where(scale > 0, scale, 1.0)) * scale
        return q.reshape(-1)[:flat.shape[0]].reshape(x.shape)

    formats = {"f32": lambda x: x, "bf16w": bf16, "int8w": int8}
    if precision not in formats:
        raise ValueError(f"weight precision {precision!r}")
    return {k: v if k in F32_LEAVES else formats[precision](v)
            for k, v in w.items()}


def _mm(compute):
    """The projections' matrix product: ``f32`` (at ``highest``), or
    ``bf16`` (operands rounded, float32 sums)."""
    if compute == "f32":
        return lambda x, w: jnp.matmul(x, w,
                                       precision=jax.lax.Precision.HIGHEST)
    if compute == "bf16":
        return lambda x, w: jnp.matmul(
            x.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
            preferred_element_type=jnp.float32)
    raise ValueError(f"precision {compute!r}")


def _layer_norm(x, g, eps):
    """Cohere's LayerNorm: the mean taken out, a gain, no bias."""
    xc = x - jnp.mean(x, axis=-1, keepdims=True)
    return xc * jax.lax.rsqrt(jnp.mean(jnp.square(xc), axis=-1,
                                       keepdims=True) + eps) * g


def inv_freq(config):
    s = dims(config)
    j = np.arange(s["hd"] // 2, dtype=np.float64)
    return (s["theta"] ** (-2.0 * j / s["hd"])).astype(np.float32)


def _rotate_interleaved(x, cos, sin):
    """The source's pairing (``rope_gptj``): channels ``(2j, 2j + 1)`` turn
    together."""
    pairs = x.reshape(x.shape[:-1] + (-1, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape)


def _attention(u, w, s, mm, kind, freq, band=True, rotary=None):
    """u [T, D] (normed) -> [T, D]: grouped-query causal attention, a head
    at a time (a ``[T, T]`` float32 score block a head), the window written
    as a mask. ``band`` False leaves the window out and ``rotary`` forces
    positions on or off: the CPU tests' broken references."""
    T, H, KVH, hd = u.shape[0], s["H"], s["KVH"], s["hd"]
    hi = jax.lax.Precision.HIGHEST
    q = mm(u, w["q_proj"]).reshape(T, H, hd)
    k = mm(u, w["k_proj"]).reshape(T, KVH, hd)
    v = mm(u, w["v_proj"]).reshape(T, KVH, hd)
    if (kind == SLIDING) if rotary is None else rotary:
        angle = jnp.arange(T, dtype=jnp.float32)[:, None] * freq[None, :]
        cos, sin = jnp.cos(angle)[:, None], jnp.sin(angle)[:, None]
        q, k = _rotate_interleaved(q, cos, sin), \
            _rotate_interleaved(k, cos, sin)
    i, j = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
    seen = j <= i
    if kind == SLIDING and band:
        seen &= i - j < s["W"]
    scale = hd ** -0.5

    def head(h):
        g = h // (H // KVH)
        sc = jnp.matmul(q[:, h], k[:, g].T, precision=hi) * scale
        p = jax.nn.softmax(jnp.where(seen, sc, -jnp.inf), axis=-1)
        return jnp.matmul(p, v[:, g], precision=hi)

    att = jax.lax.map(head, jnp.arange(H))               # [H, T, hd]
    return mm(jnp.moveaxis(att, 0, 1).reshape(T, H * hd), w["o_proj"])


def _gated(u, gate, up, down, mm):
    return mm(jax.nn.silu(mm(u, gate)) * mm(u, up), down)


def route(u, w, s):
    """u [T, D] -> (experts [T, k], weights [T, k]): float32 throughout,
    whatever the projections' precision: the source computes it so."""
    hi = jax.lax.Precision.HIGHEST
    score = jax.nn.sigmoid(jnp.matmul(u, w["gate"], precision=hi))
    weights, experts = jax.lax.top_k(score, s["k"])
    return experts, weights / jnp.sum(weights, axis=1, keepdims=True)


def _ffn(u, w, s, mm):
    """The chip's share: every held expert over every token, weighted by
    what the router gave it there (0 where it was not chosen), plus the
    mean of the shared experts. Returns (ffn [T, D], [T, G] which held
    experts a token chose)."""
    experts, weights = route(u, w, s)

    def one(y, xs):
        g, gate, up, down = xs
        w_g = jnp.sum(jnp.where(experts == s["first"] + g, weights, 0.0),
                      axis=1)
        return y + w_g[:, None] * _gated(u, gate, up, down, mm), w_g > 0

    y, chose = jax.lax.scan(
        one, jnp.zeros_like(u),
        (jnp.arange(s["G"]), w["experts_gate_proj"], w["experts_up_proj"],
         w["experts_down_proj"]))

    def shared(y, xs):
        return y + _gated(u, *xs, mm), None

    mean, _ = jax.lax.scan(shared, jnp.zeros_like(u), (
        w["shared_gate_proj"], w["shared_up_proj"], w["shared_down_proj"]))
    return y + mean / s["S"], chose.T


def _layer(x, w, s, mm, kind, freq, **broken):
    """The parallel block: one norm, both halves added side by side."""
    u = _layer_norm(x, w["input_layernorm"], s["eps"])
    ffn, chose = _ffn(u, w, s, mm)
    return x + _attention(u, w, s, mm, kind, freq, **broken) + ffn, chose


def forward(config, seed, tokens, held="f32", compute="f32", **broken):
    """tokens [T] -> logits [T, V], float32: the whole share on one
    sequence, layer by layer (the CPU tests' plain forward pass).
    ``broken``: ``band=False`` or ``rotary=True`` for every layer, the
    tests' references that lack the mechanism."""
    hidden = _hidden(config, seed, [np.asarray(tokens, np.int32)[None]],
                     held, compute, **broken)[0][0][0]
    return _head(config, seed, held, compute)(hidden)


_SHAPE_KEYS = ("hidden_size", "num_attention_heads", "num_key_value_heads",
               "head_dim", "num_hidden_layers", "intermediate_size",
               "num_shared_experts", "num_experts", "first_expert",
               "num_experts_per_tok", "vocab_size", "sliding_window",
               "layer_norm_eps", "rope_theta", "logit_scale")


def _shape_of(config):
    """The keys that shape the programs, hashable."""
    return (tuple((k, config.get(k)) for k in _SHAPE_KEYS)
            + (("published_experts", config["published"]["num_experts"]),
               ("layer_types", tuple(config["layer_types"]))))


def _config_of(shape):
    config = dict(shape)
    config["published"] = {"num_experts": config.pop("published_experts")}
    return config


@functools.lru_cache(maxsize=None)
def _programs(shape, held, compute, broken=()):
    """The reference's jitted pieces for one configuration and precision;
    the key of the weights is an argument of each draw."""
    config = _config_of(shape)
    s = dims(config)
    mm = _mm(compute)
    freq = jnp.asarray(inv_freq(config))

    def logits(top, hidden):
        h = _layer_norm(hidden, top["norm"], s["eps"])
        return mm(h, top["embed_tokens"].T) * s["logit_scale"]

    out = {"top": jax.jit(lambda key: round_weights(
               top_weights(key, config), held)),
           "embed": jax.jit(lambda top, tokens:
                            top["embed_tokens"][tokens]),
           "logits": jax.jit(logits),
           "draw": jax.jit(lambda key, i: round_weights(
               layer_weights(key, config, i), held))}
    for kind in (SLIDING, FULL):
        out["apply", kind] = jax.jit(
            lambda w, x, kind=kind: jax.vmap(lambda row: _layer(
                row, w, s, mm, kind, freq, **dict(broken)))(x))
    return out


def _hidden(config, seed, blocks, held, compute, **broken):
    """Every block of token rows ``[n, T]`` through embedding and all the
    layers, one layer's weights on the device at a time. Returns (the
    blocks' hidden states ``[n, T, D]`` before the final norm, per layer
    the blocks' ``[n, T, G]`` held experts chosen)."""
    key = _key(seed)
    fns = _programs(_shape_of(config), held, compute,
                    tuple(sorted(broken.items())))
    top = fns["top"](key)
    xs = [fns["embed"](top, jnp.asarray(b)) for b in blocks]
    del top
    chosen = []
    for i, kind in enumerate(layer_kinds(config)):
        w = fns["draw"](key, jnp.int32(i))       # this layer's, then gone
        out = [fns["apply", kind](w, x) for x in xs]
        xs = [o[0] for o in out]
        chosen.append([np.asarray(o[1]) for o in out])
        del w, out
    return xs, chosen


def _head(config, seed, held, compute):
    fns = _programs(_shape_of(config), held, compute)
    top = fns["top"](_key(seed))
    return lambda hidden: fns["logits"](top, hidden)


def _reference_serve(config, seed, samples, pads, rows, columns,
                     chosen_by=()):
    """As ``kimi_k2._reference_serve``: for each sample ``(prompt, served)``
    one forward over prompt and served tokens, padded to the smallest of
    ``pads`` that holds them. Returns {"gaps": {"served": [...], <control>:
    ...}, "logits": {"reference": [...], <control>: ...}}. A control also
    prints how often its routing differs from the float32 pass's in an
    expert this chip holds."""
    pads = sorted(pads)
    columns = jnp.asarray(columns, jnp.int32)
    feeds, groups = [], {}
    for n_sample, (prompt, served) in enumerate(samples):
        n, k = len(prompt), len(served)
        fit = [p for p in pads if p >= n + k]
        if not fit or k > rows:
            raise ValueError(f"sample of {n}+{k} tokens exceeds the "
                             f"reference's padding {pads[-1]}/{rows}")
        tokens = np.zeros((fit[0],), np.int32)
        tokens[:n + k] = list(prompt) + list(served)
        at = np.zeros((rows,), np.int32)
        at[:k] = np.arange(n - 1, n + k - 1)
        feeds.append((tokens, at, k, n + k))
        groups.setdefault(fit[0], []).append(n_sample)
    blocks, members = [], []
    big, small = BLOCKS
    for pad, ids in sorted(groups.items()):
        while ids:
            size = big if len(ids) >= big else small
            part, ids = ids[:size], ids[size:]
            members.append(part)
            blocks.append(np.stack([feeds[i][0] for i in
                                    part + [part[0]] * (size - len(part))]))

    routes = {}

    def served_rows(held, compute):
        """Per sample the hidden states at its served positions [rows, D];
        remembers the pass's routing of every real token."""
        out = [None] * len(samples)
        hidden, chosen = _hidden(config, seed, blocks, held, compute)
        for b, (part, h) in enumerate(zip(members, hidden)):
            for j, i in enumerate(part):
                out[i] = h[j][feeds[i][1]]
        routes[held, compute] = [
            np.concatenate([layer[b][j, :feeds[i][3]]
                            for b, part in enumerate(members)
                            for j, i in enumerate(part)])
            for layer in chosen]
        return out

    @jax.jit
    def first_and_columns(logits, columns):
        return jnp.argmax(logits, axis=-1), logits[:, columns]

    @jax.jit
    def gaps_and_columns(logits, picked, columns):
        best = jnp.max(logits, axis=-1)
        gaps = best[None] - jnp.take_along_axis(logits, picked.T, axis=-1).T
        return gaps, logits[:, columns]

    chosen = {"served": [np.asarray(served, np.int32)
                         for _, served in samples]}
    logits = {}
    for name in chosen_by:                       # one model at a time
        held, _, compute = name.partition("+")
        head = _head(config, seed, held, compute or "f32")
        got = [first_and_columns(head(h), columns)
               for h in served_rows(held, compute or "f32")]
        chosen[name] = [np.asarray(first)[:k]
                        for (first, _), (_, _, k, _) in zip(got, feeds)]
        logits[name] = [np.asarray(cols)[:k]
                        for (_, cols), (_, _, k, _) in zip(got, feeds)]
        del got, head
        _free_device_memory()
    names = list(chosen)
    gaps = {name: [] for name in names}
    logits["reference"] = []
    head = _head(config, seed, "f32", "f32")
    for i, h in enumerate(served_rows("f32", "f32")):
        k = feeds[i][2]
        picked = np.zeros((len(names), rows), np.int32)
        for j, name in enumerate(names):
            picked[j, :k] = chosen[name][i]
        g, cols = gaps_and_columns(head(h), picked, columns)
        g = np.asarray(g, np.float64)
        for j, name in enumerate(names):
            gaps[name].append(g[j, :k])
        logits["reference"].append(np.asarray(cols)[:k])
    del head
    _free_device_memory()
    truth = routes["f32", "f32"]
    for (held, compute), other in routes.items():
        if (held, compute) == ("f32", "f32"):
            continue
        flips = [float(np.mean(np.any(a != b, axis=1)))
                 for a, b in zip(truth, other)]
        print(f"[bench] tokens whose choice of a held expert differs from "
              f"the float32 pass's, by layer, {held}+{compute}: "
              + " ".join(f"{f:.4f}" for f in flips), flush=True)
    return {"gaps": gaps, "logits": logits}


def reference(config, mode, seed, precision="f32", **kw):
    if mode != "serve":
        raise ValueError(f"mode {mode!r}: expected one of {MODES}")
    with jax.default_matmul_precision("highest"):
        return _reference_serve(config, seed, kw["samples"], kw["pads"],
                                kw["rows"], kw["columns"],
                                kw.get("chosen_by", ()))
