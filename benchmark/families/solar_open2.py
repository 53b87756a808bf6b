"""The Solar-Open2 family (``model_type: solar_open2``): Kimi Delta Attention
layers (a delta rule whose gate is a vector over the key channels) and gated
grouped-query attention layers in the pattern ``gqa_layers`` lists, a
pre-norm block, sigmoid-routed experts with one shared expert in every layer,
no positions, an untied head; as ONE chip's share of an expert-parallel group.

Two halves that share nothing but the seeded weights (as ``cohere2_moe.py``):

* ``build`` hands the weights to the program under test
  (``paddle_tpu.models.solar_open2`` through ``DecodeEngine`` -> ``Scheduler``
  -> ``EngineLoop``; serving only: the delta rule has no backward pass) and
  returns the object the timed window drives;
* ``reference`` is the plain model: ``jax.numpy`` in float32 under
  ``default_matmul_precision("highest")``, the delta rule the token-by-token
  recurrence in a ``lax.scan`` (no chunks), full causal softmax a head at a
  time, every held expert dense over every token weighted by the router, no
  kernel, no cache. It imports nothing of the program and draws its weights
  from the seed **a layer at a time** (a layer's held share is 3.1 GB in
  float32). The same pass with the weights rounded (``int8w``: 8 bits, one
  scale per 256 values, the engine quantiser's granularity) is the control
  that ``correct`` has to refuse.

The layer (``x`` the residual stream, every norm an RMSNorm with a gain,
``rms_norm_eps``): ``h = x + Mixer(input_layernorm x)``, ``out = h +
MoE(post_attention_layernorm h)``; after the last layer ``norm`` and ``logits
= hidden @ lm_head``.

*KDA* (Kimi Delta Attention, arXiv:2510.26692; ``linear_attn_config``: ``H``
heads, keys and values of ``head_dim``, a depthwise causal conv of
``short_conv_kernel_size`` without bias): ``q~, k~, v~ = silu(conv(u W_q)),
silu(conv(u W_k)), silu(conv(u W_v))``; a head ``q = l2norm(q~) / sqrt(dk)``,
``k = l2norm(k~)``; the log-gate A CHANNEL ``g = -exp(A_log[h]) softplus((u
f_a) f_b + dt_bias)`` in ``R^{H x dk}`` (two steps through ``head_dim``:
``kda_use_full_proj: false``), ``alpha = exp(g)``; ``beta = 2 sigmoid(u
b_proj)`` a head (the 2 is ``kda_allow_neg_eigval``); the state a head ``S in
R^{dk x dv}``: ``S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t
k_t v_t^T``, ``o_t = S_t^T q_t``; out ``(rmsnorm(o_t; gain over dv) *
sigmoid((u g_a) g_b)) o_proj``.

*GQA*: ``H`` query heads over ``KVH`` key/value heads of ``head_dim``, query
head ``i`` on key/value head ``i // (H / KVH)``, no rotary and no positions
(``use_rope: false``), causal softmax at ``head_dim ^ -0.5``, and an output
gate a value (``use_gqa_gate``): ``(Attn(u) * sigmoid(u g_proj)) o_proj``.

*Experts*: ``s = sigmoid(u gate)`` over ALL published experts in float32, the
``num_experts_per_tok`` largest of ``s + e_score_correction_bias`` chosen,
weights ``s`` at the chosen over their sum times ``routed_scaling_factor``;
``MoE(u) = sum over chosen experts THIS CHIP HOLDS of w_k E_k(u) + S(u)``,
every expert and the shared one ``down(silu(gate u) * up u)`` of
``moe_intermediate_size`` (the shared one ``n_shared_experts`` times that).

**Departures from the source** (each listed under ``assumed`` in the
configuration file): the GQA gate's elementwise sigmoid form (the config
gives the flag alone); sigmoid scores with a selection bias (the config's key
names are the DeepSeek-V3 family's); the pre-norm arrangement; the conv
without bias; the l2 norm's epsilon 1e-6; the output norm a head; float32
state; every leaf's initialisation.

**The share.** The configuration holds ``n_routed_experts`` experts from
``first_expert`` on, of ``published.n_routed_experts`` the router scores; that
partial sum is what goes on to the next layer, in the program and here alike:
nothing stands in for the other chips or their exchange. ``vocab_size`` rows
of embedding and head are held, from row 0.
"""
import functools
import gc
import math

import jax
import jax.numpy as jnp
import numpy as np

MODES = ("serve",)
# the configuration keys that are widths: ``reduced`` may name none of them
# (``linear_attn_config`` is the KDA layers' group: heads, head_dim, conv;
# the router's published width is ``published.n_routed_experts``:
# ``n_routed_experts`` at the top level counts the experts HELD)
WIDTH_KEYS = ("hidden_size", "num_attention_heads", "num_key_value_heads",
              "head_dim", "linear_attn_config", "moe_intermediate_size",
              "intermediate_size", "num_experts_per_tok", "n_shared_experts")
# samples the reference takes through a layer in one call: one, where a
# sample is up to 11k tokens through 40 dense experts
BLOCKS = (1, 1)
KDA, GQA = "kda", "gqa"
KDA_CHUNK = 64              # the program's chunk: what its prefill counts in


# ---------------------------------------------------------------------------
# sizes and counts (from the configuration file's published keys)
# ---------------------------------------------------------------------------

def dims(config):
    lin = config["linear_attn_config"]
    L = int(config["num_hidden_layers"])
    gqa = [int(l) for l in config["gqa_layers"]]
    if [l for l in gqa if not 0 <= l < L] or len(set(gqa)) != len(gqa):
        raise ValueError(f"gqa_layers {gqa}: distinct layers of {L}")
    return {"L": L, "Ld": int(config.get("first_k_dense_replace", 0)),
            "kinds": [GQA if l in gqa else KDA for l in range(L)],
            "D": int(config["hidden_size"]),
            "H": int(config["num_attention_heads"]),
            "KVH": int(config["num_key_value_heads"]),
            "hd": int(config["head_dim"]),
            "Hk": int(lin["num_heads"]), "dk": int(lin["head_dim"]),
            "K": int(lin["short_conv_kernel_size"]),
            "F": int(config["moe_intermediate_size"]),
            "S": int(config["n_shared_experts"]),
            # the router's width is the published count, whatever is held
            "E": int(config["published"]["n_routed_experts"]),
            "G": int(config["n_routed_experts"]),
            "first": int(config.get("first_expert", 0)),
            "k": int(config["num_experts_per_tok"]),
            "scale": float(config["routed_scaling_factor"]),
            "V": int(config["vocab_size"]),
            "beta_max": 2.0 if config["kda_allow_neg_eigval"] else 1.0,
            "eps": float(config["rms_norm_eps"])}


def layer_kinds(config):
    """"kda" or "gqa" for every layer, from ``gqa_layers``."""
    return dims(config)["kinds"]


def leaf_shapes(config, kind):
    """One layer's leaves; matrices ``[in, out]``, a conv ``[channels,
    taps]`` (tap ``K - 1`` multiplies the current token), the held experts'
    stacked ``[n, in, out]``."""
    s = dims(config)
    D, F, Fs = s["D"], s["F"], s["F"] * s["S"]
    block = {"input_layernorm": (D,), "post_attention_layernorm": (D,),
             "gate": (D, s["E"]), "e_score_correction_bias": (s["E"],),
             "shared_gate_proj": (D, Fs), "shared_up_proj": (D, Fs),
             "shared_down_proj": (Fs, D),
             "experts_gate_proj": (s["G"], D, F),
             "experts_up_proj": (s["G"], D, F),
             "experts_down_proj": (s["G"], F, D)}
    if kind == GQA:
        n, kv = s["H"] * s["hd"], s["KVH"] * s["hd"]
        return {"q_proj": (D, n), "k_proj": (D, kv), "v_proj": (D, kv),
                "g_proj": (D, n), "o_proj": (n, D), **block}
    n, r = s["Hk"] * s["dk"], s["dk"]
    return {"q_proj": (D, n), "k_proj": (D, n), "v_proj": (D, n),
            "q_conv1d": (n, s["K"]), "k_conv1d": (n, s["K"]),
            "v_conv1d": (n, s["K"]), "f_a_proj": (D, r), "f_b_proj": (r, n),
            "dt_bias": (n,), "A_log": (s["Hk"],), "b_proj": (D, s["Hk"]),
            "g_a_proj": (D, r), "g_b_proj": (r, n), "o_norm": (s["dk"],),
            "o_proj": (n, D), **block}


TOP_SHAPES = {"embed_tokens": ("V", "D"), "norm": ("D",),
              "lm_head": ("D", "V")}
GAINS = ("input_layernorm", "post_attention_layernorm", "o_norm", "norm")
OUT_PROJECTIONS = ("o_proj", "shared_down_proj", "experts_down_proj")
EXPERTS = ("experts_gate_proj", "experts_up_proj", "experts_down_proj")
# what stays float32 whatever the weights' format: gains, the conv's taps,
# the decay's constants, and the router with its bias
F32_LEAVES = GAINS + ("q_conv1d", "k_conv1d", "v_conv1d", "A_log", "dt_bias",
                      "gate", "e_score_correction_bias")


def _count(shapes, skip=()):
    return sum(int(np.prod(v)) for k, v in shapes.items() if k not in skip)


def expert_params(config):
    """Parameters of ONE routed expert (gate, up and down)."""
    s = dims(config)
    return 3 * s["D"] * s["F"]


def param_count(config):
    s = dims(config)
    return 2 * s["V"] * s["D"] + s["D"] + sum(
        _count(leaf_shapes(config, kind)) for kind in s["kinds"])


def dense_params_per_step(config):
    """(matrix parameters every tick multiplies whatever it routes: each
    layer's mixer and shared expert, and the head; float32 parameters every
    tick reads: routers with their biases, gains, taps, decay constants).
    The held experts are counted by how many a tick hits, the embedding by
    the rows it reads."""
    s = dims(config)
    held = s["V"] * s["D"]
    small = s["D"]
    for kind in s["kinds"]:
        shapes = leaf_shapes(config, kind)
        f32 = _count({k: v for k, v in shapes.items() if k in F32_LEAVES})
        held += _count(shapes, skip=EXPERTS) - f32
        small += f32
    return held, small


def kv_bytes_per_token(config, cache_bytes=2):
    """Keys and values a cached token holds: every key/value head of every
    GQA layer."""
    s = dims(config)
    return s["kinds"].count(GQA) * 2 * s["KVH"] * s["hd"] * cache_bytes


def kda_state_bytes(config):
    """One sequence's matrix states, float32: what the update kernel has to
    read and to write for a rider."""
    s = dims(config)
    return s["kinds"].count(KDA) * s["Hk"] * s["dk"] * s["dk"] * 4


def state_bytes_per_sequence(config, conv_bytes=2):
    """What one sequence carries between calls beside its pages: a KDA
    layer's matrix states in float32 and the conv's last ``K - 1``
    inputs."""
    s = dims(config)
    conv = 3 * s["Hk"] * s["dk"] * (s["K"] - 1)
    return (kda_state_bytes(config)
            + s["kinds"].count(KDA) * conv * conv_bytes)


def bytes_per_decode_step(config, experts_hit, state_bytes, cached_tokens,
                          riders, weight_bytes=2, cache_bytes=2):
    """Least bytes of one decode tick: every non-expert matrix and the head
    once, the float32 leaves, the held experts that got a token
    (``experts_hit``, summed over layers) once each, the riders' embedding
    rows, the riders' recurrent state (matrix states and conv taps) read and
    written back, and the keys and values of the riders' cached tokens."""
    s = dims(config)
    held, small = dense_params_per_step(config)
    return (held * weight_bytes + small * 4
            + int(experts_hit) * expert_params(config) * weight_bytes
            + int(riders) * s["D"] * weight_bytes
            + 2 * int(state_bytes)
            + int(cached_tokens) * kv_bytes_per_token(config, cache_bytes))


def state_update_bytes(config, riders):
    """Bytes the one-token delta rule has to move in a tick of ``riders``:
    each rider's matrix states read once and written once."""
    return 2 * int(riders) * kda_state_bytes(config)


def chunk_prefill_flops(config, tokens, chunk=KDA_CHUNK):
    """Matrix-product operations of the chunkwise delta rule for ``tokens``
    prompt tokens, all KDA layers: a chunk of ``C`` tokens and head the two
    score matrices and ``T (beta exp(G) K)`` (``2 C^2 dk`` each), ``T (beta
    V)`` and the masked scores times ``U`` (``2 C^2 dv`` each), and three
    products with the state (``2 C dk dv`` each). The triangular solve and
    the diagonal sub-blocks' pairwise differences are the VPU's and are not
    counted."""
    s = dims(config)
    dk = dv = s["dk"]
    per_token = 2 * chunk * (3 * dk + 2 * dv) + 6 * dk * dv
    return s["kinds"].count(KDA) * s["Hk"] * per_token * int(tokens)


def chunk_prefill_bytes(config, tokens, sequences, act_bytes=2):
    """Bytes the chunkwise delta rule has to move: a token, head and layer
    ``q``, ``k``, ``v`` read and ``o`` written (``dk`` values each), the
    log-gate a channel (``dk`` float32) and ``beta`` read; a sequence, head
    and layer the final state written once (float32)."""
    s = dims(config)
    dk = s["dk"]
    per_token = 4 * dk * act_bytes + 4 * dk + 4
    return s["kinds"].count(KDA) * s["Hk"] * (
        int(tokens) * per_token + int(sequences) * dk * dk * 4)


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


# ---------------------------------------------------------------------------
# seeded weights: drawn leaf by leaf, keyed by (seed, layer, leaf name)
# ---------------------------------------------------------------------------

def _key(seed):
    seed = int(seed) % (1 << 62)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


_LEAF_IDS = {name: i for i, name in enumerate((
    "A_log", "b_proj", "dt_bias", "e_score_correction_bias", "embed_tokens",
    "experts_down_proj", "experts_gate_proj", "experts_up_proj", "f_a_proj",
    "f_b_proj", "g_a_proj", "g_b_proj", "g_proj", "gate", "input_layernorm",
    "k_conv1d", "k_proj", "lm_head", "norm", "o_norm", "o_proj",
    "post_attention_layernorm", "q_conv1d", "q_proj", "shared_down_proj",
    "shared_gate_proj", "shared_up_proj", "v_conv1d", "v_proj"))}


def _draw_leaf(key, layer, name, shape, s):
    """One float32 leaf (``s``: ``dims``); ``key`` and ``layer`` may be
    traced. Projections N(0, 0.02); out-projections (``o_proj`` and every
    ``down``) scaled by ``1 / sqrt(2 L)``; gains ``1 + N(0, 0.02)``; the
    router's selection bias N(0, 0.002) (not zero, so that what is selected
    and how it is weighted differ, and small against the spacing of the
    scores it reorders: ``kimi_k2.py``); the conv's taps as PyTorch's
    ``Conv1d`` (uniform in +-1/sqrt(K)); and the delta rule's init for the
    decay: ``A`` uniform in [1, 16] and logged, a head, ``dt`` log-uniform
    in [1e-3, 1e-1] through the inverse softplus, a channel, so that states
    decay at the rates a trained model's do."""
    k = jax.random.fold_in(jax.random.fold_in(key, layer), _LEAF_IDS[name])
    std = 0.02
    if name == "A_log":
        return jnp.log(jax.random.uniform(k, shape, jnp.float32, 1.0, 16.0))
    if name == "dt_bias":
        u = jax.random.uniform(k, shape, jnp.float32)
        dt = jnp.exp(u * (math.log(1e-1) - math.log(1e-3)) + math.log(1e-3))
        return dt + jnp.log(-jnp.expm1(-dt))        # inverse softplus
    if name.endswith("conv1d"):
        bound = 1.0 / math.sqrt(s["K"])
        return jax.random.uniform(k, shape, jnp.float32, -bound, bound)
    z = jax.random.normal(k, shape, jnp.float32)
    if name in GAINS:
        return 1.0 + std * z
    if name == "e_score_correction_bias":
        return 0.002 * z
    if name in OUT_PROJECTIONS:
        return z * (std / math.sqrt(2 * s["L"]))
    return z * std


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


# leaf of the program's stored tree -> the leaf here it is (a conv
# transposed to [taps, channels])
_PROGRAM_FFN = {"norm2": "post_attention_layernorm", "router": "gate",
                "router_bias": "e_score_correction_bias",
                "shared_gate": "shared_gate_proj",
                "shared_up": "shared_up_proj",
                "shared_down": "shared_down_proj",
                "w_down": "experts_down_proj"}
_PROGRAM_KDA = {"norm1": "input_layernorm", "w_q": "q_proj", "w_k": "k_proj",
                "w_v": "v_proj", "conv_q": "q_conv1d", "conv_k": "k_conv1d",
                "conv_v": "v_conv1d", "w_fa": "f_a_proj", "w_fb": "f_b_proj",
                "dt_bias": "dt_bias", "A_log": "A_log", "w_beta": "b_proj",
                "w_ga": "g_a_proj", "w_gb": "g_b_proj", "o_norm": "o_norm",
                "w_o": "o_proj", **_PROGRAM_FFN}
_PROGRAM_GQA = {"norm1": "input_layernorm", "w_q": "q_proj", "w_k": "k_proj",
                "w_v": "v_proj", "w_gate": "g_proj", "w_o": "o_proj",
                **_PROGRAM_FFN}


def program_weights(seed, config, dtype):
    """The same draws in the program's stored tree
    (``models/solar_open2.py:leaf_shapes``): a dict a layer, matrices in
    ``dtype``, :data:`F32_LEAVES` float32, each routed expert's gate and up
    side by side. One jitted call a leaf (compiled once a leaf name and
    shape: the layer is an argument), so no float32 copy of more than one
    leaf is ever held."""
    key = _key(seed)
    s = dims(config)

    @functools.lru_cache(maxsize=None)
    def drawer(name, shape):
        held = jnp.float32 if name in F32_LEAVES else dtype

        def one(key, i):
            x = _draw_leaf(key, i, name, shape, s)
            return (x.T if name.endswith("conv1d") else x).astype(held)

        return jax.jit(one)

    def draw(name, shape, i):
        return drawer(name, tuple(shape))(key, jnp.int32(i))

    layers = []
    for i, kind in enumerate(s["kinds"]):
        shapes = leaf_shapes(config, kind)
        names = _PROGRAM_GQA if kind == GQA else _PROGRAM_KDA
        layer = {p: draw(n, shapes[n], i) for p, n in names.items()}
        layer["w_gate_up"] = jnp.concatenate(
            [draw(n, shapes[n], i) for n in
             ("experts_gate_proj", "experts_up_proj")], axis=-1)
        layers.append(layer)
    top = {name: draw(name, tuple(s[d] for d in shape), s["L"])
           for name, shape in TOP_SHAPES.items()}
    return {"embed": top["embed_tokens"], "final_norm": top["norm"],
            "lm_head": top["lm_head"], "layers": layers}


# ---------------------------------------------------------------------------
# the program under test
# ---------------------------------------------------------------------------

class ServeProgram:
    """``DecodeEngine`` -> ``warmup`` -> ``Scheduler`` -> ``EngineLoop``,
    driven in process: the entry points the other serving cells use."""

    def __init__(self, config, devices, seed):
        from paddle_tpu import serving
        from paddle_tpu.models.solar_open2 import SolarOpen2Config
        from paddle_tpu.serving.server import EngineLoop

        sv = config["serving"]
        engine_kw = dict(sv["engine"])
        if "prefill_buckets" in engine_kw:
            engine_kw["prefill_buckets"] = tuple(engine_kw["prefill_buckets"])
        s = dims(config)
        self.cfg = SolarOpen2Config(
            vocab_size=s["V"], hidden_size=s["D"], num_hidden_layers=s["L"],
            gqa_layers=tuple(int(l) for l in config["gqa_layers"]),
            num_attention_heads=s["H"], num_key_value_heads=s["KVH"],
            head_dim=s["hd"], linear_num_heads=s["Hk"],
            linear_head_dim=s["dk"], short_conv_kernel_size=s["K"],
            kda_use_full_proj=bool(config["kda_use_full_proj"]),
            kda_allow_neg_eigval=bool(config["kda_allow_neg_eigval"]),
            use_gqa_gate=bool(config["use_gqa_gate"]),
            use_rope=bool(config["use_rope"]),
            first_k_dense_replace=s["Ld"], moe_intermediate_size=s["F"],
            n_routed_experts_published=s["E"], experts_held=s["G"],
            first_expert=s["first"], num_experts_per_tok=s["k"],
            n_shared_experts=s["S"],
            norm_topk_prob=bool(config["norm_topk_prob"]),
            routed_scaling_factor=s["scale"], rms_norm_eps=s["eps"],
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
    raise ValueError(f"mode {mode!r}: expected one of {MODES} (the delta "
                     "rule and the experts have no backward pass: this "
                     "family is not trained)")


def _free_device_memory():
    gc.collect()
    jax.clear_caches()


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------

def round_weights(w, precision):
    """Every matrix of ``w`` as a weight-only format would hold it
    (``bf16w``; ``int8w``: 8 bits, one scale per 256 values, the engine
    quantiser's granularity); :data:`F32_LEAVES` stay float32, as the
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


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1,
                                      keepdims=True) + eps) * g


def kda_rule(q, k, v, alpha, beta):
    """The recurrence itself, token by token from a zero state, in the
    source's form: q, k, v ``[T, H, d]``, alpha ``[T, H, d]`` (a gate a key
    channel), beta ``[T, H]`` -> ``(o [T, H, d], S [H, dk, dv])``."""
    hi = jax.lax.Precision.HIGHEST

    def step(S, t):
        q_t, k_t, v_t, a_t, b_t = t
        S = a_t[:, :, None] * S                     # Diag(alpha) S
        kS = jnp.einsum("hk,hkv->hv", k_t, S, precision=hi)
        S = S - b_t[:, None, None] * k_t[:, :, None] * kS[:, None, :] \
            + b_t[:, None, None] * k_t[:, :, None] * v_t[:, None, :]
        return S, jnp.einsum("hk,hkv->hv", q_t, S, precision=hi)

    H, dk, dv = q.shape[1], q.shape[2], v.shape[2]
    S, o = jax.lax.scan(step, jnp.zeros((H, dk, dv), jnp.float32),
                        (q, k, v, alpha, beta))
    return o, S


def kda_inputs(u, w, s, mm):
    """u [T, D] (normed) -> (q, k, v [T, H, d], alpha [T, H, d], beta [T,
    H])."""
    T = u.shape[0]
    H, d, K = s["Hk"], s["dk"], s["K"]

    def conv_silu(y, taps):                 # y [T, C], taps [C, K]
        padded = jnp.concatenate([jnp.zeros((K - 1, y.shape[1]), y.dtype),
                                  y])
        return jax.nn.silu(sum(padded[j:j + T] * taps[:, j][None, :]
                               for j in range(K)))

    def l2norm(y):
        return y * jax.lax.rsqrt(jnp.sum(y * y, axis=-1, keepdims=True)
                                 + 1e-6)

    q = conv_silu(mm(u, w["q_proj"]), w["q_conv1d"]).reshape(T, H, d)
    k = conv_silu(mm(u, w["k_proj"]), w["k_conv1d"]).reshape(T, H, d)
    v = conv_silu(mm(u, w["v_proj"]), w["v_conv1d"]).reshape(T, H, d)
    beta = s["beta_max"] * jax.nn.sigmoid(mm(u, w["b_proj"]))
    g = -jnp.exp(w["A_log"])[None, :, None] * jax.nn.softplus(
        mm(mm(u, w["f_a_proj"]), w["f_b_proj"]) + w["dt_bias"]
    ).reshape(T, H, d)
    return l2norm(q) / math.sqrt(d), l2norm(k), v, jnp.exp(g), beta


def _kda_mixer(u, w, s, mm, scalar_gate=False):
    """u [T, D] -> [T, D]. ``scalar_gate``: a head's channels all take the
    mean of their log-gates, the delta rule with ONE gate a head (the CPU
    tests' broken reference)."""
    T = u.shape[0]
    q, k, v, alpha, beta = kda_inputs(u, w, s, mm)
    if scalar_gate:
        alpha = jnp.broadcast_to(jnp.exp(jnp.mean(
            jnp.log(alpha), axis=-1, keepdims=True)), alpha.shape)
    o, _ = kda_rule(q, k, v, alpha, beta)
    gate = jax.nn.sigmoid(mm(mm(u, w["g_a_proj"]), w["g_b_proj"]))
    o = _rms(o, w["o_norm"], s["eps"]).reshape(T, -1) * gate
    return mm(o, w["o_proj"])


def _gqa_mixer(u, w, s, mm, gated=True):
    """u [T, D] -> [T, D]: grouped-query causal attention a head at a time
    (a ``[T, T]`` float32 score block a head), no positions, the output
    gate a value. ``gated`` False leaves the gate out (the CPU tests'
    broken reference)."""
    T, H, KVH, hd = u.shape[0], s["H"], s["KVH"], s["hd"]
    hi = jax.lax.Precision.HIGHEST
    q = mm(u, w["q_proj"]).reshape(T, H, hd)
    k = mm(u, w["k_proj"]).reshape(T, KVH, hd)
    v = mm(u, w["v_proj"]).reshape(T, KVH, hd)
    seen = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]
    scale = hd ** -0.5

    def head(h):
        g = h // (H // KVH)
        sc = jnp.matmul(q[:, h], k[:, g].T, precision=hi) * scale
        p = jax.nn.softmax(jnp.where(seen, sc, -jnp.inf), axis=-1)
        return jnp.matmul(p, v[:, g], precision=hi)

    att = jax.lax.map(head, jnp.arange(H))               # [H, T, hd]
    att = jnp.moveaxis(att, 0, 1).reshape(T, H * hd)
    if gated:
        att = att * jax.nn.sigmoid(mm(u, w["g_proj"]))
    return mm(att, w["o_proj"])


def _gated(u, gate, up, down, mm):
    return mm(jax.nn.silu(mm(u, gate)) * mm(u, up), down)


def route(u, w, s):
    """u [T, D] -> (experts [T, k], weights [T, k]): float32 throughout,
    whatever the projections' precision: the source computes it so."""
    hi = jax.lax.Precision.HIGHEST
    score = jax.nn.sigmoid(jnp.matmul(u, w["gate"], precision=hi))
    _, experts = jax.lax.top_k(score + w["e_score_correction_bias"], s["k"])
    weights = jnp.take_along_axis(score, experts, axis=1)
    return experts, weights / jnp.sum(weights, axis=1,
                                      keepdims=True) * s["scale"]


def _moe(u, w, s, mm):
    """The chip's share: every held expert over every token, weighted by
    what the router gave it there (0 where it was not chosen), plus the
    shared expert. Returns (moe [T, D], [T, G] which held experts a token
    chose)."""
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
    shared = _gated(u, w["shared_gate_proj"], w["shared_up_proj"],
                    w["shared_down_proj"], mm)
    return y + shared, chose.T


def _layer(x, w, s, mm, kind, **broken):
    """The pre-norm block round a mixer and the experts. ``broken``:
    ``gated=False`` for a GQA layer, ``scalar_gate=True`` for a KDA one."""
    u = _rms(x, w["input_layernorm"], s["eps"])
    mixer, flag = ((_gqa_mixer, "gated") if kind == GQA
                   else (_kda_mixer, "scalar_gate"))
    h = x + mixer(u, w, s, mm, **{k: v for k, v in broken.items()
                                  if k == flag})
    moe, chose = _moe(_rms(h, w["post_attention_layernorm"], s["eps"]), w, s,
                      mm)
    return h + moe, chose


def forward(config, seed, tokens, held="f32", compute="f32", **broken):
    """tokens [T] -> logits [T, V], float32: the whole share on one
    sequence, layer by layer (the CPU tests' plain forward pass).
    ``broken``: ``scalar_gate=True`` or ``gated=False`` for every layer, the
    tests' references that lack the mechanism."""
    hidden = _hidden(config, seed, [np.asarray(tokens, np.int32)[None]],
                     held, compute, **broken)[0][0][0]
    return _head(config, seed, held, compute)(hidden)


_SHAPE_KEYS = ("hidden_size", "num_attention_heads", "num_key_value_heads",
               "head_dim", "num_hidden_layers", "moe_intermediate_size",
               "n_shared_experts", "n_routed_experts", "first_expert",
               "num_experts_per_tok", "routed_scaling_factor", "vocab_size",
               "kda_allow_neg_eigval", "rms_norm_eps",
               "first_k_dense_replace")


def _shape_of(config):
    """The keys that shape the programs, hashable."""
    return (tuple((k, config.get(k)) for k in _SHAPE_KEYS)
            + (("published_experts", config["published"]["n_routed_experts"]),
               ("gqa_layers", tuple(config["gqa_layers"])),
               ("linear_attn_config", tuple(sorted(
                   config["linear_attn_config"].items())))))


def _config_of(shape):
    config = dict(shape)
    config["published"] = {
        "n_routed_experts": config.pop("published_experts")}
    config["linear_attn_config"] = dict(config["linear_attn_config"])
    return config


@functools.lru_cache(maxsize=None)
def _programs(shape, held, compute, broken=()):
    """The reference's jitted pieces for one configuration and precision;
    the key of the weights is an argument of each draw."""
    config = _config_of(shape)
    s = dims(config)
    mm = _mm(compute)

    def logits(top, hidden):
        return mm(_rms(hidden, top["norm"], s["eps"]), top["lm_head"])

    out = {"top": jax.jit(lambda key: round_weights(
               top_weights(key, config), held)),
           "embed": jax.jit(lambda top, tokens:
                            top["embed_tokens"][tokens]),
           "logits": jax.jit(logits)}
    for kind in (KDA, GQA):
        out["draw", kind] = jax.jit(
            lambda key, i, kind=kind: round_weights(
                layer_weights(key, config, kind, i), held))
        out["apply", kind] = jax.jit(
            lambda w, x, kind=kind: jax.vmap(lambda row: _layer(
                row, w, s, mm, kind, **dict(broken)))(x))
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
        w = fns["draw", kind](key, jnp.int32(i))   # this layer's, then gone
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
    """As ``cohere2_moe._reference_serve``: for each sample ``(prompt,
    served)`` one forward over prompt and served tokens, padded to the
    smallest of ``pads`` that holds them (causal and recurrent forward in
    time, so the padding changes nothing before it). Returns {"gaps":
    {"served": [...], <control>: ...}, "logits": {"reference": [...],
    <control>: ...}}. A control also prints how often its routing differs
    from the float32 pass's in an expert this chip holds."""
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
        for part, h in zip(members, hidden):
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
