"""The Olmo-Hybrid family: gated delta-rule linear-attention layers and
full multi-head attention layers in a published pattern (``layer_types``),
the OLMo 2/3 post-norm block, a gated (SwiGLU) MLP in every layer, no
positions, an untied head.

Two halves that share nothing but the seeded weights (as ``jamba.py``):

* ``build`` hands the weights to the program under test
  (``paddle_tpu.models.olmo_hybrid`` through ``DecodeEngine`` ->
  ``Scheduler`` -> ``EngineLoop``; serving only: the delta rule has no
  backward pass) and returns the object the timed window drives;
* ``reference`` is the plain model: ``jax.numpy`` in float32 under
  ``default_matmul_precision("highest")``, the delta rule the token-by-token
  recurrence in a ``lax.scan`` (no chunks), attention a head at a time, no
  kernel, no cache. It imports nothing of the program. It draws its
  weights from the seed **a layer at a time** (a layer is 0.86 GB in
  float32, the model 16 GB). The same pass with the weights rounded
  (``int8w``: 8 bits, one scale per 256 values, the engine quantiser's
  granularity) is the control that ``correct`` has to refuse.

With ``x`` the residual stream and every norm an RMSNorm with a gain (the
OLMo 2/3 arrangement: nothing is normed on the way in, every sublayer's
output is normed before it is added; an assumption, the config does not
say): ``h = x + post_attention_layernorm(mixer(x))``, ``out = h +
post_feedforward_layernorm(down(silu(gate(h)) * up(h)))``; after the last
layer ``norm`` and ``logits = hidden @ lm_head``. The linear mixer is Gated
DeltaNet (Yang, Kautz, Hatamizadeh, arXiv:2412.06464), the config's keys
its sizes: ``q~ = x W_q``, ``k~ = x W_k``, ``v~ = x W_v``, each through a
depthwise causal conv of width 4 (no bias) and SiLU; per head ``q =
l2norm(q~) / sqrt(dk)``, ``k = l2norm(k~)``; ``beta = 2 sigmoid(x W_b)``
(the 2 is ``linear_allow_neg_eigval``), ``alpha = exp(-exp(A_log)
softplus(x W_a + dt_bias))``; ``S_t = alpha_t S_{t-1} (I - beta_t k_t
k_t^T) + beta_t v_t k_t^T``, ``o_t = S_t q_t``; ``(rmsnorm(o_t; gain over
dv) * silu(x W_g)) W_o``. Full attention: ``q = rmsnorm(x W_q)``, ``k =
rmsnorm(x W_k)`` over the whole projections (QK-norm as OLMo 2/3), 30
heads of 128, every one a key/value head, causal softmax, no rotary (the
config publishes ``rope_theta: null``), no bias.
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
              "num_key_value_heads", "linear_num_key_heads",
              "linear_num_value_heads", "linear_key_head_dim",
              "linear_value_head_dim", "linear_conv_kernel_dim")
# samples the reference takes through a layer in one call: fours, and the
# rest of a padding's group one by one (fixed sizes, so that two compiled
# programs a padding serve every run): four rows of 4608 tokens through
# the MLP's 11008 columns are 2.4 GB of float32 temporaries
BLOCKS = (4, 1)
DELTA_CHUNK = 64            # the program's chunk: what its prefill counts in


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
            "Hk": int(config["linear_num_key_heads"]),
            "Hv": int(config["linear_num_value_heads"]),
            "dk": int(config["linear_key_head_dim"]),
            "dv": int(config["linear_value_head_dim"]),
            "K": int(config["linear_conv_kernel_dim"]),
            "beta_max": 2.0 if config["linear_allow_neg_eigval"] else 1.0,
            "eps": float(config["rms_norm_eps"])}


def layer_kinds(config):
    """"linear" or "full" for every layer, from ``layer_types``."""
    kinds = [{"linear_attention": "linear", "full_attention": "full"}[t]
             for t in config["layer_types"]]
    if len(kinds) != int(config["num_hidden_layers"]):
        raise ValueError("layer_types names every layer")
    return kinds


def leaf_shapes(config, kind):
    """One layer's leaves; matrices ``[in, out]``, a conv ``[channels,
    taps]`` (tap ``K - 1`` multiplies the current token)."""
    s = dims(config)
    D, F, K = s["D"], s["F"], s["K"]
    block = {"post_attention_layernorm": (D,),
             "post_feedforward_layernorm": (D,),
             "gate_proj": (D, F), "up_proj": (D, F), "down_proj": (F, D)}
    if kind == "full":
        n = s["H"] * s["hd"]
        return {"q_proj": (D, n), "k_proj": (D, s["KVH"] * s["hd"]),
                "v_proj": (D, s["KVH"] * s["hd"]), "o_proj": (n, D),
                "q_norm": (n,), "k_norm": (s["KVH"] * s["hd"],), **block}
    nk, nv = s["Hk"] * s["dk"], s["Hv"] * s["dv"]
    return {"q_proj": (D, nk), "k_proj": (D, nk), "v_proj": (D, nv),
            "q_conv1d": (nk, K), "k_conv1d": (nk, K), "v_conv1d": (nv, K),
            "a_proj": (D, s["Hv"]), "b_proj": (D, s["Hv"]),
            "A_log": (s["Hv"],), "dt_bias": (s["Hv"],),
            "g_proj": (D, nv), "o_norm": (s["dv"],), "o_proj": (nv, D),
            **block}


TOP_SHAPES = {"embed_tokens": ("V", "D"), "norm": ("D",),
              "lm_head": ("D", "V")}
MATRICES = ("q_proj", "k_proj", "v_proj", "a_proj", "b_proj", "g_proj",
            "o_proj", "gate_proj", "up_proj", "down_proj")


def param_count(config):
    s = dims(config)
    total = 2 * s["V"] * s["D"] + s["D"]
    for kind in layer_kinds(config):
        total += sum(int(np.prod(x))
                     for x in leaf_shapes(config, kind).values())
    return total


def matmul_param_count(config):
    """Parameters multiplied as matrices for every token: each layer's
    projections and the head (the embedding is a lookup)."""
    s = dims(config)
    total = s["V"] * s["D"]
    for kind in layer_kinds(config):
        shapes = leaf_shapes(config, kind)
        total += sum(int(np.prod(shapes[k])) for k in MATRICES
                     if k in shapes)
    return total


def kv_bytes_per_token(config, cache_bytes=2):
    """Keys and values a cached token holds: the heads the model has (the
    pool's two zero head rows are the layout's, no work)."""
    s = dims(config)
    return (layer_kinds(config).count("full") * 2 * s["KVH"] * s["hd"]
            * cache_bytes)


def delta_state_bytes(config):
    """One sequence's matrix states, float32: what the update kernel has
    to read and to write for a rider."""
    s = dims(config)
    return (layer_kinds(config).count("linear")
            * s["Hv"] * s["dk"] * s["dv"] * 4)


def state_bytes_per_sequence(config, conv_bytes=2):
    """What one sequence carries between calls beside its pages: a linear
    layer's matrix states in float32 and the conv's last ``K - 1``
    inputs."""
    s = dims(config)
    conv = (2 * s["Hk"] * s["dk"] + s["Hv"] * s["dv"]) * (s["K"] - 1)
    return (delta_state_bytes(config)
            + layer_kinds(config).count("linear") * conv * conv_bytes)


def bytes_per_gdn_decode_step(config, cached_tokens, state_bytes,
                              weight_bytes=2, cache_bytes=2):
    """Bytes one decode tick has to move: every multiplied weight once,
    the riders' recurrent state read and written back, the keys and
    values of the riders' cached tokens read."""
    return (matmul_param_count(config) * weight_bytes + 2 * int(state_bytes)
            + int(cached_tokens) * kv_bytes_per_token(config, cache_bytes))


def state_update_bytes(config, riders):
    """Bytes the one-token delta rule has to move in a tick of ``riders``:
    each rider's matrix states read once and written once."""
    return 2 * int(riders) * delta_state_bytes(config)


def chunk_prefill_flops(config, tokens, chunk=DELTA_CHUNK):
    """Matrix-product operations of the chunkwise delta rule for
    ``tokens`` prompt tokens, all linear layers: a chunk of ``C`` tokens
    and head ``K K^T``, ``Q K^T`` and ``T (beta gamma K)`` (``2 C^2 dk``
    each), ``T (beta V)`` and the masked scores times ``U`` (``2 C^2 dv``
    each), and three products with the state (``2 C dk dv`` each). The
    triangular solve is the VPU's and is not counted."""
    s = dims(config)
    per_token = 2 * chunk * (3 * s["dk"] + 2 * s["dv"]) \
        + 6 * s["dk"] * s["dv"]
    return layer_kinds(config).count("linear") * s["Hv"] * per_token \
        * int(tokens)


def chunk_prefill_bytes(config, tokens, sequences, act_bytes=2):
    """Bytes the chunkwise delta rule has to move: a token, head and layer
    ``q``, ``k`` read (``dk`` each), ``v`` read and ``o`` written (``dv``
    each), ``log alpha`` and ``beta`` read (float32); a sequence, head and
    layer the final state written once (float32)."""
    s = dims(config)
    per_token = (2 * s["dk"] + 2 * s["dv"]) * act_bytes + 8
    return layer_kinds(config).count("linear") * s["Hv"] * (
        int(tokens) * per_token + int(sequences) * s["dk"] * s["dv"] * 4)


# ---------------------------------------------------------------------------
# seeded weights: drawn leaf by leaf, keyed by (seed, layer, leaf name)
# ---------------------------------------------------------------------------

def _key(seed):
    seed = int(seed) % (1 << 62)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


_LEAF_IDS = {name: i for i, name in enumerate((
    "A_log", "a_proj", "b_proj", "down_proj", "dt_bias", "embed_tokens",
    "g_proj", "gate_proj", "k_conv1d", "k_norm", "k_proj", "lm_head",
    "norm", "o_norm", "o_proj", "post_attention_layernorm",
    "post_feedforward_layernorm", "q_conv1d", "q_norm", "q_proj",
    "up_proj", "v_conv1d", "v_proj"))}


def _draw_leaf(key, layer, name, shape, s):
    """One float32 leaf (``s``: ``dims``). ``key`` and ``layer`` may be
    traced: the program's stacked leaves are drawn under ``vmap`` over the
    layer index and come out as the reference's layer-at-a-time draws, and
    a key that is an argument compiles once for every seed. Every matrix
    N(0, 0.02) (the OLMo 2 init; every sublayer's output is normed, so no
    out-projection is scaled by depth), gains ``1 + N(0, 0.02)``, the
    conv's taps as PyTorch's ``Conv1d`` (uniform in +-1/sqrt(K)), and
    Gated DeltaNet's init for the decay: ``A`` uniform in [0, 16] and
    logged (held off 0 at 1e-4: a draw of exactly 0 has no logarithm),
    ``dt`` log-uniform in [1e-3, 1e-1] through the inverse softplus, so
    that states decay at the rates a trained model's do."""
    k = jax.random.fold_in(jax.random.fold_in(key, layer), _LEAF_IDS[name])
    std = 0.02
    if name.endswith("norm"):
        return 1.0 + std * jax.random.normal(k, shape, jnp.float32)
    if name == "A_log":
        a = jax.random.uniform(k, shape, jnp.float32, 0.0, 16.0)
        return jnp.log(jnp.maximum(a, 1e-4))
    if name == "dt_bias":
        u = jax.random.uniform(k, shape, jnp.float32)
        dt = jnp.exp(u * (math.log(1e-1) - math.log(1e-3)) + math.log(1e-3))
        return dt + jnp.log(-jnp.expm1(-dt))        # inverse softplus
    if name.endswith("conv1d"):
        bound = 1.0 / math.sqrt(s["K"])
        return jax.random.uniform(k, shape, jnp.float32, -bound, bound)
    return jax.random.normal(k, shape, jnp.float32) * std


def layer_weights(key, config, kind, i):
    """The leaves of layer ``i`` (of ``kind``), float32."""
    s = dims(config)
    return {name: _draw_leaf(key, i, name, shape, s)
            for name, shape in leaf_shapes(config, kind).items()}


def top_weights(key, config):
    s = dims(config)
    return {name: _draw_leaf(key, s["L"], name,
                             tuple(s[d] for d in shape), s)
            for name, shape in TOP_SHAPES.items()}


# leaf of the program's tree -> the leaves here it is made of (side by
# side on the last axis; a conv transposed to [taps, channels])
_PROGRAM_MLP = {"norm_ff": ("post_feedforward_layernorm",),
                "gate": ("gate_proj",), "up": ("up_proj",),
                "down": ("down_proj",)}
_PROGRAM_LINEAR = {"w_qkv": ("q_proj", "k_proj", "v_proj"),
                   "conv_w": ("q_conv1d", "k_conv1d", "v_conv1d"),
                   "w_ab": ("a_proj", "b_proj"), "A_log": ("A_log",),
                   "dt_bias": ("dt_bias",), "w_g": ("g_proj",),
                   "o_norm": ("o_norm",), "w_o": ("o_proj",),
                   "norm_mix": ("post_attention_layernorm",),
                   **_PROGRAM_MLP}
_PROGRAM_FULL = {"wq": ("q_proj",), "wk": ("k_proj",), "wv": ("v_proj",),
                 "wo": ("o_proj",), "q_norm": ("q_norm",),
                 "k_norm": ("k_norm",),
                 "norm_mix": ("post_attention_layernorm",), **_PROGRAM_MLP}


def program_weights(seed, config, dtype):
    """The same draws in the program's tree: linear layers stacked on a
    leading axis, full layers a list, matrices in ``dtype``, gains, conv
    taps and the decay's constants float32. One jitted call a leaf, so no
    float32 copy of a whole model is ever held."""
    key = _key(seed)
    kinds = layer_kinds(config)
    s = dims(config)
    linear_ids = jnp.asarray([i for i, k in enumerate(kinds)
                              if k == "linear"], jnp.int32)

    def leaf(names, shapes):
        """``draw(key, layers)`` of one leaf of the program's tree. Each
        part is drawn by a program of its own and the parts are joined
        after: two draws of 30 columns joined inside one program are a
        compile the TPU's compiler does not finish (my chip run, PR 35:
        stopped after 14 minutes at 37 GB of host memory)."""
        held = dtype if names[0] in MATRICES else jnp.float32

        def part(name):
            def one(key, i):
                x = _draw_leaf(key, i, name, shapes[name], s)
                return (x.T if name.endswith("conv1d") else x).astype(held)
            return one

        def draw(key, layers):
            over = (jax.vmap(part(n), in_axes=(None, 0)) if layers.ndim
                    else part(n) for n in names)
            parts = [jax.jit(f)(key, layers) for f in over]
            return parts[0] if len(parts) == 1 else jnp.concatenate(parts,
                                                                    -1)
        return draw

    l_shapes = leaf_shapes(config, "linear")
    f_shapes = leaf_shapes(config, "full")
    top = {name: jax.jit(lambda key, name=name: _draw_leaf(
               key, s["L"], name, tuple(s[d] for d in shape), s).astype(
                   jnp.float32 if name == "norm" else dtype))(key)
           for name, shape in TOP_SHAPES.items()}
    return {
        "embed": top["embed_tokens"], "final_norm": top["norm"],
        "lm_head": top["lm_head"],
        "linear": {p: leaf(n, l_shapes)(key, linear_ids)
                   for p, n in _PROGRAM_LINEAR.items()},
        "full": [{p: leaf(n, f_shapes)(key, jnp.int32(i))
                  for p, n in _PROGRAM_FULL.items()}
                 for i, k in enumerate(kinds) if k == "full"]}


# ---------------------------------------------------------------------------
# the program under test
# ---------------------------------------------------------------------------

class ServeProgram:
    """``DecodeEngine`` -> ``warmup`` -> ``Scheduler`` -> ``EngineLoop``,
    driven in process: the entry points every serving cell uses."""

    def __init__(self, config, devices, seed):
        from paddle_tpu import serving
        from paddle_tpu.models.olmo_hybrid import OlmoHybridConfig
        from paddle_tpu.serving.server import EngineLoop

        sv = config["serving"]
        engine_kw = dict(sv["engine"])
        if "prefill_buckets" in engine_kw:
            engine_kw["prefill_buckets"] = tuple(engine_kw["prefill_buckets"])
        s = dims(config)
        self.cfg = OlmoHybridConfig(
            vocab_size=s["V"], hidden_size=s["D"],
            intermediate_size=s["F"], num_hidden_layers=s["L"],
            num_attention_heads=s["H"], num_key_value_heads=s["KVH"],
            head_dim=s["hd"], layer_types=tuple(config["layer_types"]),
            linear_num_key_heads=s["Hk"], linear_num_value_heads=s["Hv"],
            linear_key_head_dim=s["dk"], linear_value_head_dim=s["dv"],
            linear_conv_kernel_dim=s["K"],
            linear_allow_neg_eigval=bool(config["linear_allow_neg_eigval"]),
            rms_norm_eps=s["eps"], dtype=jnp.dtype(sv["compute_dtype"]))
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
    raise ValueError(f"mode {mode!r}: expected one of {MODES} (the delta "
                     "rule has no backward pass: this family is not "
                     "trained)")


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


def _block(x, mixed, w, eps, mm):
    """The OLMo 2/3 block round a mixer's output."""
    h = x + _rms(mixed, w["post_attention_layernorm"], eps)
    y = mm(jax.nn.silu(mm(h, w["gate_proj"])) * mm(h, w["up_proj"]),
           w["down_proj"])
    return h + _rms(y, w["post_feedforward_layernorm"], eps)


def _full_layer(x, w, s, mm):
    """x [T, D] -> [T, D]: QK-norm over the whole projections, causal
    softmax a head at a time (30 x T x T float32 scores at once are 2.5 GB
    at T = 4608)."""
    T = x.shape[0]
    H, hd, eps = s["H"], s["hd"], s["eps"]
    q = _rms(mm(x, w["q_proj"]), w["q_norm"], eps).reshape(T, H, hd)
    k = _rms(mm(x, w["k_proj"]), w["k_norm"], eps).reshape(T, H, hd)
    v = mm(x, w["v_proj"]).reshape(T, H, hd)
    hi = jax.lax.Precision.HIGHEST
    causal = jnp.tril(jnp.ones((T, T), bool))

    def head(qkv):
        q_h, k_h, v_h = qkv
        scores = jnp.matmul(q_h, k_h.T, precision=hi) / math.sqrt(hd)
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        return jnp.matmul(probs, v_h, precision=hi)

    a = jax.lax.map(head, tuple(t.transpose(1, 0, 2) for t in (q, k, v)))
    return _block(x, mm(a.transpose(1, 0, 2).reshape(T, H * hd),
                        w["o_proj"]), w, eps, mm)


def delta_rule(q, k, v, alpha, beta):
    """The recurrence itself, token by token from a zero state: q, k
    ``[T, H, dk]``, v ``[T, H, dv]``, alpha, beta ``[T, H]`` -> ``(o [T,
    H, dv], S [H, dv, dk])``."""
    hi = jax.lax.Precision.HIGHEST

    def step(S, t):
        q_t, k_t, v_t, a_t, b_t = t
        Sk = jnp.einsum("hvk,hk->hv", S, k_t, precision=hi)
        S = a_t[:, None, None] * (S - b_t[:, None, None] * Sk[:, :, None]
                                  * k_t[:, None, :]) \
            + b_t[:, None, None] * v_t[:, :, None] * k_t[:, None, :]
        return S, jnp.einsum("hvk,hk->hv", S, q_t, precision=hi)

    H, dk, dv = q.shape[1], q.shape[2], v.shape[2]
    S, o = jax.lax.scan(step, jnp.zeros((H, dv, dk), jnp.float32),
                        (q, k, v, alpha, beta))
    return o, S


def delta_inputs(x, w, s, mm):
    """x [T, D] -> (q, k [T, H, dk], v [T, H, dv], alpha, beta [T, H])."""
    T = x.shape[0]
    H, dk, dv, K = s["Hv"], s["dk"], s["dv"], s["K"]

    def conv_silu(y, taps):                 # y [T, C], taps [C, K]
        padded = jnp.concatenate([jnp.zeros((K - 1, y.shape[1]), y.dtype),
                                  y])
        return jax.nn.silu(sum(padded[j:j + T] * taps[:, j][None, :]
                               for j in range(K)))

    def l2norm(y):
        return y * jax.lax.rsqrt(jnp.sum(y * y, axis=-1, keepdims=True)
                                 + 1e-6)

    q = conv_silu(mm(x, w["q_proj"]), w["q_conv1d"]).reshape(T, H, dk)
    k = conv_silu(mm(x, w["k_proj"]), w["k_conv1d"]).reshape(T, H, dk)
    v = conv_silu(mm(x, w["v_proj"]), w["v_conv1d"]).reshape(T, H, dv)
    beta = s["beta_max"] * jax.nn.sigmoid(mm(x, w["b_proj"]))
    alpha = jnp.exp(-jnp.exp(w["A_log"]) * jax.nn.softplus(
        mm(x, w["a_proj"]) + w["dt_bias"]))
    return l2norm(q) / math.sqrt(dk), l2norm(k), v, alpha, beta


def _linear_layer(x, w, s, mm):
    """x [T, D] -> [T, D]: the published mixer, the delta rule a
    ``lax.scan`` over tokens from a zero state."""
    T = x.shape[0]
    o, _ = delta_rule(*delta_inputs(x, w, s, mm))
    o = _rms(o, w["o_norm"], s["eps"]) \
        * jax.nn.silu(mm(x, w["g_proj"])).reshape(o.shape)
    return _block(x, mm(o.reshape(T, -1), w["o_proj"]), w, s["eps"], mm)


_LAYERS = {"full": _full_layer, "linear": _linear_layer}


def forward(config, seed, tokens, held="f32", compute="f32"):
    """tokens [T] -> logits [T, V], float32: the whole model on one
    sequence, layer by layer (the CPU tests' plain forward pass)."""
    hidden = _hidden(config, seed, [np.asarray(tokens, np.int32)[None]],
                     held, compute)[0][0]
    return _head(config, seed, held, compute)(hidden)


_SHAPE_KEYS = ("hidden_size", "num_attention_heads", "num_key_value_heads",
               "head_dim", "num_hidden_layers", "intermediate_size",
               "vocab_size", "linear_num_key_heads", "linear_num_value_heads",
               "linear_key_head_dim", "linear_value_head_dim",
               "linear_conv_kernel_dim", "linear_allow_neg_eigval",
               "rms_norm_eps")


def _shape_of(config):
    """The keys that shape the programs, hashable: one set of jitted
    functions serves every call on the same sizes."""
    return tuple((k, config.get(k)) for k in _SHAPE_KEYS) + (
        ("layer_types", tuple(config["layer_types"])),)


@functools.lru_cache(maxsize=None)
def _programs(shape, held, compute):
    """{"top", "embed", "logits", ("draw", kind), ("apply", kind)}: the
    reference's jitted pieces for one configuration and precision. The
    key of the weights is an argument of each draw, never a constant."""
    config = dict(shape)
    s = dims(config)
    mm = _mm(compute)

    def logits(top, hidden):
        h = _rms(hidden, top["norm"], s["eps"])
        return mm(h, top["lm_head"])

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
    """``hidden [rows, D] -> logits [rows, V]`` with the head and the
    final norm drawn once."""
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
