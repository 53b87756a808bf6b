"""The Jamba family: Mamba-1 and attention layers in a published pattern,
RMSNorm, a gated (SwiGLU) MLP in every layer, no positions, a tied head.

Two halves that share nothing but the seeded weights (as ``gpt2.py``):

* ``build`` hands the weights to the program under test
  (``paddle_tpu.models.jamba`` through ``DecodeEngine`` -> ``Scheduler`` ->
  ``EngineLoop``; serving only: the scan has no backward pass) and returns
  the object the timed window drives;
* ``reference`` is the plain model, following HF ``modeling_jamba.py``:
  ``jax.numpy`` in float32 under ``default_matmul_precision("highest")``,
  the scan a ``lax.scan`` over tokens, no kernel, no cache. It imports
  nothing of the program. It draws its weights from the seed **a layer at
  a time**: 12.1 GB of float32 weights do not sit beside their activations
  on one chip, so every sampled request goes through one layer, then the
  next. The same pass with the weights rounded (``int8w``: 8 bits, one
  scale per 256 values, the engine quantiser's granularity) is the control
  that ``correct`` has to refuse.

Layer ``i`` is attention iff ``i % attn_layer_period ==
attn_layer_offset``. With ``x`` the residual stream and every norm an
RMSNorm with a gain: ``h = x + mixer(norm_in(x))``, ``out = h +
down(silu(gate(u)) * up(u))`` with ``u = norm_ff(h)``; after the last
layer ``final_norm`` and ``logits = hidden @ embed.T``. The Mamba mixer:
``[xs, z] = u W_in``; ``xs = silu(causal_depthwise_conv1d(xs) + bias)``;
``[dt, B, C] = xs W_x``, each RMS-normed with its own gain (Jamba's inner
norms); ``delta = softplus(dt W_dt + dt_bias)``; ``A = -exp(A_log)``;
``h_t = exp(delta_t A) h_{t-1} + (delta_t xs_t) outer B_t``; ``y_t = h_t .
C_t + D xs_t``; ``y = y * silu(z)``; ``y W_out``. Attention: 20 query heads
over 1 key/value head of 128, causal softmax, no rotary, no bias.
"""
import functools
import gc
import math

import jax
import jax.numpy as jnp
import numpy as np

MODES = ("serve",)
# the configuration keys that are widths: ``reduced`` may name none of them
WIDTH_KEYS = ("hidden_size", "intermediate_size", "num_attention_heads",
              "num_key_value_heads", "mamba_d_state", "mamba_d_conv",
              "mamba_expand", "mamba_dt_rank")
# samples the reference takes through a layer in one call: eights, and
# the rest of a padding's group in pairs (fixed sizes, so that two compiled
# programs a padding serve every run, and at most one row a group is a
# filler)
BLOCKS = (8, 2)


# ---------------------------------------------------------------------------
# sizes and counts (from the configuration file's published keys)
# ---------------------------------------------------------------------------

def dims(config):
    d = int(config["hidden_size"])
    h = int(config["num_attention_heads"])
    return {"L": int(config["num_hidden_layers"]), "D": d, "H": h,
            "KVH": int(config["num_key_value_heads"]),
            "hd": int(config.get("head_dim") or d // h),
            "F": int(config["intermediate_size"]),
            "V": int(config["vocab_size"]),
            "Di": int(config["mamba_expand"]) * d,
            "N": int(config["mamba_d_state"]),
            "K": int(config["mamba_d_conv"]),
            "R": int(config["mamba_dt_rank"]),
            "eps": float(config["rms_norm_eps"])}


def is_attention(config, i):
    return (i % int(config["attn_layer_period"])
            == int(config["attn_layer_offset"]))


def layer_kinds(config):
    return ["attention" if is_attention(config, i) else "mamba"
            for i in range(dims(config)["L"])]


def leaf_shapes(config, kind):
    """One layer's leaves in the published names; matrices ``[in, out]``,
    ``conv1d_w`` ``[Di, K]`` (tap ``K - 1`` multiplies the current token),
    ``A_log`` ``[Di, N]``."""
    s = dims(config)
    D, F, Di, N, K, R = s["D"], s["F"], s["Di"], s["N"], s["K"], s["R"]
    mlp = {"input_layernorm": (D,), "pre_ff_layernorm": (D,),
           "gate_proj": (D, F), "up_proj": (D, F), "down_proj": (F, D)}
    if kind == "attention":
        return {"q_proj": (D, s["H"] * s["hd"]),
                "k_proj": (D, s["KVH"] * s["hd"]),
                "v_proj": (D, s["KVH"] * s["hd"]),
                "o_proj": (s["H"] * s["hd"], D), **mlp}
    return {"in_proj": (D, 2 * Di), "conv1d_w": (Di, K), "conv1d_b": (Di,),
            "x_proj": (Di, R + 2 * N), "dt_layernorm": (R,),
            "b_layernorm": (N,), "c_layernorm": (N,), "dt_proj": (R, Di),
            "dt_bias": (Di,), "A_log": (Di, N), "D": (Di,),
            "out_proj": (Di, D), **mlp}


TOP_SHAPES = {"embed_tokens": ("V", "D"), "final_layernorm": ("D",)}
MATRICES = ("in_proj", "x_proj", "dt_proj", "out_proj", "q_proj", "k_proj",
            "v_proj", "o_proj", "gate_proj", "up_proj", "down_proj")


def param_count(config):
    s = dims(config)
    total = s["V"] * s["D"] + s["D"]
    for kind in layer_kinds(config):
        total += sum(int(np.prod(x))
                     for x in leaf_shapes(config, kind).values())
    return total


def matmul_param_count(config):
    """Parameters multiplied as matrices for every token: each layer's
    projections and the tied matrix once, as the head (the embedding is a
    lookup of the same matrix)."""
    s = dims(config)
    total = s["V"] * s["D"]
    for kind in layer_kinds(config):
        shapes = leaf_shapes(config, kind)
        total += sum(int(np.prod(shapes[k])) for k in MATRICES
                     if k in shapes)
    return total


def kv_bytes_per_token(config, cache_bytes=2):
    s = dims(config)
    return (layer_kinds(config).count("attention") * 2 * s["KVH"] * s["hd"]
            * cache_bytes)


def state_bytes_per_sequence(config, conv_bytes=2):
    """What one sequence carries between calls: a Mamba layer's scan state
    in float32 and the conv's last ``K - 1`` inputs."""
    s = dims(config)
    return layer_kinds(config).count("mamba") * (
        s["Di"] * s["N"] * 4 + s["Di"] * (s["K"] - 1) * conv_bytes)


def bytes_per_ssm_decode_step(config, cached_tokens, state_bytes,
                              weight_bytes=2, cache_bytes=2):
    """Bytes one decode tick has to move: every multiplied weight once
    (the tied matrix once), the riders' recurrent state read and written
    back, the keys and values of the riders' cached tokens read."""
    return (matmul_param_count(config) * weight_bytes + 2 * int(state_bytes)
            + int(cached_tokens) * kv_bytes_per_token(config, cache_bytes))


def scan_bytes(config, scan_tokens, sequences, act_bytes=2):
    """Bytes the selective scan has to move for ``scan_tokens`` prompt
    tokens of ``sequences`` prompts: a token and Mamba layer ``xs``,
    ``delta``, ``z`` read and ``y`` written (``Di`` each) and ``B``, ``C``
    read (``N`` each); a sequence and layer the final state written once
    (float32). The padding of a rung is no work the algorithm requires."""
    s = dims(config)
    lm = layer_kinds(config).count("mamba")
    per_token = (4 * s["Di"] + 2 * s["N"]) * act_bytes
    return lm * (int(scan_tokens) * per_token
                 + int(sequences) * s["Di"] * s["N"] * 4)


# ---------------------------------------------------------------------------
# seeded weights: drawn leaf by leaf, keyed by (seed, layer, leaf name)
# ---------------------------------------------------------------------------

def _key(seed):
    seed = int(seed) % (1 << 62)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


_LEAF_IDS = {name: i for i, name in enumerate((
    "A_log", "D", "b_layernorm", "c_layernorm", "conv1d_b", "conv1d_w",
    "down_proj", "dt_bias", "dt_layernorm", "dt_proj", "embed_tokens",
    "final_layernorm", "gate_proj", "in_proj", "input_layernorm", "k_proj",
    "o_proj", "out_proj", "pre_ff_layernorm", "q_proj", "up_proj", "v_proj",
    "x_proj"))}


def _draw_leaf(key, layer, name, shape, s):
    """One float32 leaf (``s``: ``dims``). ``key`` and ``layer`` may be
    traced: the program's stacked leaves are drawn under ``vmap`` over the
    layer index and come out as the reference's layer-at-a-time draws, and
    a key that is an argument compiles once for every seed. Projections
    N(0, 0.02), out-projections scaled by ``1 / sqrt(2 L)``, gains ``1 +
    N(0, 0.02)``, and Mamba's published init for the mixer's own leaves
    (``A_log``, ``D``, ``dt_bias``, the conv as PyTorch's ``Conv1d``)."""
    k = jax.random.fold_in(jax.random.fold_in(key, layer), _LEAF_IDS[name])
    std = 0.02
    if name.endswith("layernorm"):
        return 1.0 + std * jax.random.normal(k, shape, jnp.float32)
    if name == "A_log":
        n = jnp.arange(1, shape[-1] + 1, dtype=jnp.float32)
        return jnp.broadcast_to(jnp.log(n), shape)
    if name == "D":
        return jnp.ones(shape, jnp.float32)
    if name == "dt_bias":
        u = jax.random.uniform(k, shape, jnp.float32)
        dt = jnp.exp(u * (math.log(1e-1) - math.log(1e-3)) + math.log(1e-3))
        return dt + jnp.log(-jnp.expm1(-dt))        # inverse softplus
    if name in ("conv1d_w", "conv1d_b"):
        bound = 1.0 / math.sqrt(s["K"])
        return jax.random.uniform(k, shape, jnp.float32, -bound, bound)
    z = jax.random.normal(k, shape, jnp.float32)
    if name in ("out_proj", "o_proj", "down_proj"):
        return z * (std / math.sqrt(2 * s["L"]))
    return z * std


def layer_weights(key, config, kind, i):
    """The leaves of layer ``i`` (of ``kind``), float32, published names."""
    s = dims(config)
    return {name: _draw_leaf(key, i, name, shape, s)
            for name, shape in leaf_shapes(config, kind).items()}


def top_weights(key, config):
    s = dims(config)
    return {name: _draw_leaf(key, s["L"], name,
                             tuple(s[d] for d in shape), s)
            for name, shape in TOP_SHAPES.items()}


# leaf of the program's tree -> (leaf here, transposed?)
_PROGRAM_MLP = {"norm_ff": "pre_ff_layernorm", "gate": "gate_proj",
                "up": "up_proj", "down": "down_proj"}
_PROGRAM_MAMBA = {"norm_in": "input_layernorm", "in_proj": "in_proj",
                  "conv_w": "conv1d_w", "conv_b": "conv1d_b",
                  "x_proj": "x_proj", "dt_norm": "dt_layernorm",
                  "b_norm": "b_layernorm", "c_norm": "c_layernorm",
                  "dt_proj": "dt_proj", "dt_bias": "dt_bias",
                  "A_log": "A_log", "D": "D", "out_proj": "out_proj",
                  **_PROGRAM_MLP}
_PROGRAM_ATTN = {"norm_in": "input_layernorm", "wq": "q_proj",
                 "wk": "k_proj", "wv": "v_proj", "wo": "o_proj",
                 **_PROGRAM_MLP}
_TRANSPOSED = ("conv_w", "A_log")       # the program keeps channels minor


def program_weights(seed, config, dtype):
    """The same draws in the program's tree: Mamba layers stacked on a
    leading axis, attention layers a list, matrices in ``dtype``, gains,
    biases and the scan's constants float32. One jitted call a leaf, so no
    float32 copy of a whole model is ever held."""
    key = _key(seed)
    kinds = layer_kinds(config)
    s = dims(config)
    mamba_ids = jnp.asarray([i for i, k in enumerate(kinds)
                             if k == "mamba"], jnp.int32)

    def held(name):
        return dtype if name in MATRICES else jnp.float32

    def leaf(pname, name, shape):
        def one(key, i):
            x = _draw_leaf(key, i, name, shape, s)
            return (x.T if pname in _TRANSPOSED else x).astype(held(name))
        return one

    def stacked(pname, name, shape):
        return jax.jit(jax.vmap(leaf(pname, name, shape),
                                in_axes=(None, 0)))(key, mamba_ids)

    m_shapes = leaf_shapes(config, "mamba")
    a_shapes = leaf_shapes(config, "attention")
    top = jax.jit(lambda key: top_weights(key, config))(key)
    return {
        "embed": top["embed_tokens"].astype(dtype),
        "final_norm": top["final_layernorm"],
        "mamba": {p: stacked(p, n, m_shapes[n])
                  for p, n in _PROGRAM_MAMBA.items()},
        "attn": [{p: jax.jit(leaf(p, n, a_shapes[n]))(key, jnp.int32(i))
                  for p, n in _PROGRAM_ATTN.items()}
                 for i, k in enumerate(kinds) if k == "attention"]}


# ---------------------------------------------------------------------------
# the program under test
# ---------------------------------------------------------------------------

class ServeProgram:
    """``DecodeEngine`` -> ``warmup`` -> ``Scheduler`` -> ``EngineLoop``,
    driven in process: the entry points the GPT cell uses."""

    def __init__(self, config, devices, seed):
        from paddle_tpu import serving
        from paddle_tpu.models.jamba import JambaConfig
        from paddle_tpu.serving.server import EngineLoop

        sv = config["serving"]
        engine_kw = dict(sv["engine"])
        if "prefill_buckets" in engine_kw:
            engine_kw["prefill_buckets"] = tuple(engine_kw["prefill_buckets"])
        s = dims(config)
        self.cfg = JambaConfig(
            vocab_size=s["V"], hidden_size=s["D"],
            intermediate_size=s["F"], num_hidden_layers=s["L"],
            num_attention_heads=s["H"], num_key_value_heads=s["KVH"],
            head_dim=s["hd"],
            attn_layer_period=int(config["attn_layer_period"]),
            attn_layer_offset=int(config["attn_layer_offset"]),
            mamba_d_state=s["N"], mamba_d_conv=s["K"],
            mamba_expand=int(config["mamba_expand"]),
            mamba_dt_rank=s["R"], rms_norm_eps=s["eps"],
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
        eng.cache.set_arrays((None, None, None, None))
        eng._exec.clear()
        self.engine = self.scheduler = self.loop = None
        _free_device_memory()


def build(config, mode, devices, seed):
    if mode == "serve":
        return ServeProgram(config, devices, seed)
    raise ValueError(f"mode {mode!r}: expected one of {MODES} (the scan has "
                     "no backward pass: this family is not trained)")


def _free_device_memory():
    gc.collect()
    jax.clear_caches()


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------

def round_weights(w, precision):
    """Every leaf of ``w`` as a weight-only format would hold it:
    ``bf16w`` rounds to bfloat16; ``int8w`` to 8 bits with one scale per
    256 consecutive values, the granularity of the engine's own
    quantiser. Activations and arithmetic stay float32."""
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
    return {k: formats[precision](v) for k, v in w.items()}


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


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1,
                                      keepdims=True) + eps) * g


def _mlp(h, w, eps, mm):
    u = _rms(h, w["pre_ff_layernorm"], eps)
    return h + mm(jax.nn.silu(mm(u, w["gate_proj"])) * mm(u, w["up_proj"]),
                  w["down_proj"])


def _attention_layer(x, w, s, mm):
    """x [T, D] -> [T, D]."""
    T = x.shape[0]
    H, KVH, hd = s["H"], s["KVH"], s["hd"]
    u = _rms(x, w["input_layernorm"], s["eps"])
    q = mm(u, w["q_proj"]).reshape(T, KVH, H // KVH, hd)
    k = mm(u, w["k_proj"]).reshape(T, KVH, hd)
    v = mm(u, w["v_proj"]).reshape(T, KVH, hd)
    hi = jax.lax.Precision.HIGHEST
    scores = jnp.einsum("qkgd,skd->kgqs", q, k, precision=hi) / math.sqrt(hd)
    scores = jnp.where(jnp.tril(jnp.ones((T, T), bool)), scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    a = jnp.einsum("kgqs,skd->qkgd", probs, v, precision=hi)
    return _mlp(x + mm(a.reshape(T, H * hd), w["o_proj"]), w, s["eps"], mm)


def _mamba_layer(x, w, s, mm):
    """x [T, D] -> [T, D]: the published mixer, the scan a ``lax.scan``
    over tokens from a zero state."""
    T = x.shape[0]
    Di, N, K, R, eps = s["Di"], s["N"], s["K"], s["R"], s["eps"]
    u = _rms(x, w["input_layernorm"], eps)
    xz = mm(u, w["in_proj"])
    xs, z = xz[:, :Di], xz[:, Di:]
    padded = jnp.concatenate([jnp.zeros((K - 1, Di), xs.dtype), xs])
    conv = sum(padded[k:k + T] * w["conv1d_w"][:, k][None, :]
               for k in range(K)) + w["conv1d_b"]
    xs = jax.nn.silu(conv)
    dbc = mm(xs, w["x_proj"])
    dt = _rms(dbc[:, :R], w["dt_layernorm"], eps)
    Bm = _rms(dbc[:, R:R + N], w["b_layernorm"], eps)
    Cm = _rms(dbc[:, R + N:], w["c_layernorm"], eps)
    delta = jax.nn.softplus(mm(dt, w["dt_proj"]) + w["dt_bias"])
    A = -jnp.exp(w["A_log"])                              # [Di, N]

    def step(h, t):
        d_t, x_t, b_t, c_t = t
        h = jnp.exp(d_t[:, None] * A) * h \
            + (d_t * x_t)[:, None] * b_t[None, :]
        return h, jnp.sum(h * c_t[None, :], axis=1)

    _, y = jax.lax.scan(step, jnp.zeros((Di, N), jnp.float32),
                        (delta, xs, Bm, Cm))
    y = (y + w["D"] * xs) * jax.nn.silu(z)
    return _mlp(x + mm(y, w["out_proj"]), w, eps, mm)


_LAYERS = {"attention": _attention_layer, "mamba": _mamba_layer}


def forward(config, seed, tokens, held="f32", compute="f32"):
    """tokens [T] -> logits [T, V], float32: the whole model on one
    sequence, layer by layer (the CPU tests' plain forward pass)."""
    hidden = _hidden(config, seed, [np.asarray(tokens, np.int32)[None]],
                     held, compute)[0][0]
    return _head(config, seed, held, compute)(hidden)


_SHAPE_KEYS = ("hidden_size", "num_attention_heads", "num_key_value_heads",
               "head_dim", "num_hidden_layers", "intermediate_size",
               "vocab_size", "mamba_expand", "mamba_d_state", "mamba_d_conv",
               "mamba_dt_rank", "rms_norm_eps", "attn_layer_period",
               "attn_layer_offset")


def _shape_of(config):
    """The keys that shape the programs, hashable: one set of jitted
    functions serves every call on the same sizes."""
    return tuple((k, config.get(k)) for k in _SHAPE_KEYS)


@functools.lru_cache(maxsize=None)
def _programs(shape, held, compute):
    """{"top", "embed", "logits", ("draw", kind), ("apply", kind)}: the
    reference's jitted pieces for one configuration and precision. The
    key of the weights is an argument of each draw, never a constant."""
    config = dict(shape)
    s = dims(config)
    mm = _mm(compute)

    def logits(top, hidden):
        h = _rms(hidden, top["final_layernorm"], s["eps"])
        return mm(h, top["embed_tokens"].T)

    out = {"top": jax.jit(lambda key: round_weights(
               top_weights(key, config), held)),
           "embed": jax.jit(lambda top, tokens:
                            top["embed_tokens"][tokens]),
           "logits": jax.jit(logits)}
    for kind, layer in _LAYERS.items():
        out["draw", kind] = jax.jit(
            lambda key, i, kind=kind: round_weights(
                layer_weights(key, config, kind, i), held))
        out["apply", kind] = jax.jit(
            lambda w, x, layer=layer: jax.vmap(
                lambda row: layer(row, w, s, mm))(x))
    return out


def _hidden(config, seed, blocks, held, compute):
    """Every block of token rows ``[n, T]`` through embedding and all the
    layers, one layer's weights on the device at a time. Returns the
    blocks' hidden states ``[n, T, D]`` before the final norm."""
    key = _key(seed)
    fns = _programs(_shape_of(config), held, compute)
    top = fns["top"](key)
    xs = [fns["embed"](top, jnp.asarray(b)) for b in blocks]
    del top
    for i, kind in enumerate(layer_kinds(config)):
        w = fns["draw", kind](key, jnp.int32(i))   # this layer's, then gone
        xs = [fns["apply", kind](w, x) for x in xs]
        del w
    return xs


def _head(config, seed, held, compute):
    """``hidden [rows, D] -> logits [rows, V]`` with the tied matrix and
    the final norm drawn once."""
    fns = _programs(_shape_of(config), held, compute)
    top = fns["top"](_key(seed))
    return lambda hidden: fns["logits"](top, hidden)


def _reference_serve(config, seed, samples, pads, rows, columns,
                     chosen_by=()):
    """As ``gpt2._reference_serve``: for each sample ``(prompt, served)``
    one forward over prompt and served tokens, padded to the smallest of
    ``pads`` that holds them (causal and recurrent forward in time, so the
    padding changes nothing before it). Returns {"gaps": {"served": [...],
    <control>: ...}, "logits": {"reference": [...], <control>: ...}}: at
    every served position the gap by which the served (or the control's
    first) token's logit lies below the reference's best, and the logits
    at the vocabulary ``columns``. Samples of one padding go through the
    layers in blocks of ``BLOCKS`` rows."""
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
        feeds.append((tokens, at, k))
        groups.setdefault(fit[0], []).append(n_sample)
    # a padding's samples in blocks of the larger size while they fill
    # one, then of the smaller; a short last block repeats its first row
    blocks, members = [], []
    big, small = BLOCKS
    for pad, ids in sorted(groups.items()):
        while ids:
            size = big if len(ids) >= big else small
            part, ids = ids[:size], ids[size:]
            members.append(part)
            blocks.append(np.stack([feeds[i][0] for i in
                                    part + [part[0]] * (size - len(part))]))

    def served_rows(held, compute):
        """Per sample the hidden states at its served positions [rows, D]."""
        out = [None] * len(samples)
        for part, h in zip(members, _hidden(config, seed, blocks, held,
                                            compute)):
            for j, i in enumerate(part):
                out[i] = h[j][feeds[i][1]]
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
                        for (first, _), (_, _, k) in zip(got, feeds)]
        logits[name] = [np.asarray(cols)[:k]
                        for (_, cols), (_, _, k) in zip(got, feeds)]
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
    return {"gaps": gaps, "logits": logits}


def reference(config, mode, seed, precision="f32", **kw):
    if mode != "serve":
        raise ValueError(f"mode {mode!r}: expected one of {MODES}")
    with jax.default_matmul_precision("highest"):
        return _reference_serve(config, seed, kw["samples"], kw["pads"],
                                kw["rows"], kw["columns"],
                                kw.get("chosen_by", ()))
