"""The Kimi-K2 family (``model_type: kimi_k2``, the DeepSeek-V3 block): latent
attention, sigmoid-routed experts with a shared expert, yarn rotary, RMSNorm,
an untied head; as ONE chip's share of an expert-parallel group.

Two halves that share nothing but the seeded weights (as ``jamba.py``):

* ``build`` hands the weights to the program under test
  (``paddle_tpu.models.kimi_k2`` through ``DecodeEngine`` -> ``Scheduler`` ->
  ``EngineLoop``; serving only) and returns the object the timed window
  drives;
* ``reference`` is the plain model, following HF ``modeling_deepseek.py``:
  ``jax.numpy`` in float32 under ``default_matmul_precision("highest")``,
  attention in the expanded form with interleaved rotary pairs, the experts
  a loop over the held ones with every token through each, no kernel, no
  cache. It imports nothing of the program and draws its weights from the
  seed **a layer at a time**.

The layer (``d`` hidden, ``H`` heads, pre-norm residual block, ``eps`` from
the file): ``h += Attn(norm(h))``, ``h += FFN(norm(h))``; final norm, untied
head. ``c_q = norm(x W_qa)``, ``q = c_q W_qb`` -> H x (``q_nope``, ``q_rope``);
``[c_kv | k_rope] = x W_kva``, ``c_kv = norm(c_kv)``, ``k_rope`` one row for
all heads; ``[k_nope | v] = c_kv W_kvb``; rotary (yarn ``inv_freq``) on
``q_rope`` and ``k_rope``; softmax scale ``(nope + rope)^-0.5 (0.1 ln(factor)
+ 1)^2``. The first ``first_k_dense_replace`` layers' FFN is a gated MLP of
``intermediate_size``; the others: ``s = sigmoid(x W_g)`` over ALL published
experts in float32, the ``num_experts_per_tok`` largest of ``s + b`` chosen,
weights ``s`` at the chosen, normalised, times ``routed_scaling_factor``;
``y = sum over chosen experts THIS CHIP HOLDS of w_k E_k(x) + Shared(x)``.

**The share.** The configuration holds ``n_routed_experts`` experts from
``first_expert`` on, of ``published.n_routed_experts`` the router scores;
that partial sum is what goes on to the next layer, in the program and here
alike: nothing stands in for the other chips or their exchange. ``vocab_size``
rows of the published table are held, from row 0.
"""
import functools
import gc
import math

import jax
import jax.numpy as jnp
import numpy as np

MODES = ("serve",)
# the configuration keys that are widths: ``reduced`` may name none of them
WIDTH_KEYS = ("hidden_size", "intermediate_size", "moe_intermediate_size",
              "num_attention_heads", "q_lora_rank", "kv_lora_rank",
              "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
              "num_experts_per_tok", "n_shared_experts")
# samples the reference takes through a layer in one call (as jamba.py)
BLOCKS = (4, 1)
QUERY_BLOCK = 256


# ---------------------------------------------------------------------------
# sizes and counts (from the configuration file's published keys)
# ---------------------------------------------------------------------------

def dims(config):
    nope, rope = (int(config["qk_nope_head_dim"]),
                  int(config["qk_rope_head_dim"]))
    return {"L": int(config["num_hidden_layers"]),
            "Ld": int(config["first_k_dense_replace"]),
            "D": int(config["hidden_size"]),
            "H": int(config["num_attention_heads"]),
            "Rq": int(config["q_lora_rank"]),
            "Rkv": int(config["kv_lora_rank"]),
            "nope": nope, "rope": rope, "dv": int(config["v_head_dim"]),
            "Fd": int(config["intermediate_size"]),
            "F": int(config["moe_intermediate_size"]),
            "Fs": (int(config["moe_intermediate_size"])
                   * int(config["n_shared_experts"])),
            # the router's width is the published count, whatever is held
            "E": int(config["published"]["n_routed_experts"]),
            "G": int(config["n_routed_experts"]),
            "first": int(config.get("first_expert", 0)),
            "k": int(config["num_experts_per_tok"]),
            "V": int(config["vocab_size"]),
            "scale": float(config["routed_scaling_factor"]),
            "eps": float(config["rms_norm_eps"]),
            "theta": float(config["rope_theta"])}


def layer_kinds(config):
    s = dims(config)
    return ["dense" if i < s["Ld"] else "moe" for i in range(s["L"])]


def leaf_shapes(config, kind):
    """One layer's leaves in the published names; matrices ``[in, out]``,
    the held experts' stacked ``[G, in, out]``."""
    s = dims(config)
    D, H = s["D"], s["H"]
    out = {"input_layernorm": (D,), "q_a_proj": (D, s["Rq"]),
           "q_a_layernorm": (s["Rq"],),
           "q_b_proj": (s["Rq"], H * (s["nope"] + s["rope"])),
           "kv_a_proj_with_mqa": (D, s["Rkv"] + s["rope"]),
           "kv_a_layernorm": (s["Rkv"],),
           "kv_b_proj": (s["Rkv"], H * (s["nope"] + s["dv"])),
           "o_proj": (H * s["dv"], D), "post_attention_layernorm": (D,)}
    if kind == "dense":
        return {**out, "gate_proj": (D, s["Fd"]), "up_proj": (D, s["Fd"]),
                "down_proj": (s["Fd"], D)}
    return {**out, "gate": (D, s["E"]),
            "e_score_correction_bias": (s["E"],),
            "shared_gate_proj": (D, s["Fs"]), "shared_up_proj": (D, s["Fs"]),
            "shared_down_proj": (s["Fs"], D),
            "experts_gate_proj": (s["G"], D, s["F"]),
            "experts_up_proj": (s["G"], D, s["F"]),
            "experts_down_proj": (s["G"], s["F"], D)}


TOP_SHAPES = {"embed_tokens": ("V", "D"), "norm": ("D",),
              "lm_head": ("D", "V")}
GAINS = ("input_layernorm", "q_a_layernorm", "kv_a_layernorm",
         "post_attention_layernorm", "norm")
OUT_PROJECTIONS = ("o_proj", "down_proj", "shared_down_proj",
                   "experts_down_proj")
# what stays float32 whatever the weights' format: gains, and the router
F32_LEAVES = GAINS + ("gate", "e_score_correction_bias")


def _count(shapes, names=None):
    return sum(int(np.prod(v)) for k, v in shapes.items()
               if names is None or k in names)


def expert_params(config):
    """Parameters of ONE routed expert (gate, up and down)."""
    s = dims(config)
    return 3 * s["D"] * s["F"]


def dense_params_per_step(config):
    """(matrix parameters every tick multiplies whatever it routes: all
    attention projections, the dense layers' MLP, the shared experts, the
    head; float32 router parameters): the held experts are counted by how
    many a tick hits, the embedding by the rows it reads."""
    s = dims(config)
    held, router = s["D"] * s["V"], 0
    for kind in layer_kinds(config):
        shapes = leaf_shapes(config, kind)
        held += _count(shapes, (
            "q_a_proj", "q_b_proj", "kv_a_proj_with_mqa", "kv_b_proj",
            "o_proj", "gate_proj", "up_proj", "down_proj",
            "shared_gate_proj", "shared_up_proj", "shared_down_proj"))
        router += _count(shapes, ("gate", "e_score_correction_bias"))
    return held, router


def param_count(config):
    s = dims(config)
    return 2 * s["V"] * s["D"] + s["D"] + sum(
        _count(leaf_shapes(config, kind)) for kind in layer_kinds(config))


def latent_bytes_per_token(config, cache_bytes=2):
    """What a token leaves in the cache, all layers: ``kv_lora_rank +
    qk_rope_head_dim`` values a layer."""
    s = dims(config)
    return s["L"] * (s["Rkv"] + s["rope"]) * cache_bytes


def bytes_per_moe_mla_decode_step(config, experts_hit, latent_bytes, riders,
                                  weight_bytes=2, cache_bytes=2):
    """Least bytes of one decode tick: every non-expert matrix once (the
    router's in float32), the held experts that got a token (``experts_hit``,
    summed over layers) once each, the riders' embedding rows, the latent
    rows of the riders' cached tokens read (``latent_bytes``) and the riders'
    new rows written."""
    s = dims(config)
    held, router = dense_params_per_step(config)
    return (held * weight_bytes + router * 4
            + int(experts_hit) * expert_params(config) * weight_bytes
            + int(riders) * s["D"] * weight_bytes
            + int(latent_bytes)
            + int(riders) * latent_bytes_per_token(config, cache_bytes))


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
    "e_score_correction_bias", "embed_tokens", "experts_down_proj",
    "experts_gate_proj", "experts_up_proj", "gate", "gate_proj",
    "down_proj", "input_layernorm", "kv_a_layernorm", "kv_a_proj_with_mqa",
    "kv_b_proj", "lm_head", "norm", "o_proj", "post_attention_layernorm",
    "q_a_layernorm", "q_a_proj", "q_b_proj", "shared_down_proj",
    "shared_gate_proj", "shared_up_proj", "up_proj"))}


def _draw_leaf(key, layer, name, shape, s):
    """One float32 leaf (``s``: ``dims``); ``key`` and ``layer`` may be
    traced. Projections N(0, 0.02), out-projections scaled by ``1 / sqrt(2
    L)``, gains ``1 + N(0, 0.02)``, the router's selection bias N(0, 0.002):
    not zero, so that what is selected and how it is weighted differ (it
    moves the choice at a third of the tokens), and small against the
    spacing of the scores it reorders: the eight largest of 384 sigmoids
    of N(0, 1.7) logits lie within 0.03 of 1, and a bias of that size
    decides by itself which experts are popular (N(0, 0.05) left 18-34 of
    this chip's 60 held experts with a token in a tick, by the seed's
    luck, and the tick's length with them), where the source trains the
    bias to even the load out."""
    k = jax.random.fold_in(jax.random.fold_in(key, layer), _LEAF_IDS[name])
    std = 0.02
    z = jax.random.normal(k, shape, jnp.float32)
    if name in GAINS:
        return 1.0 + std * z
    if name == "e_score_correction_bias":
        return 0.002 * z
    if name in OUT_PROJECTIONS:
        return z * (std / math.sqrt(2 * s["L"]))
    return z * std


def layer_weights(key, config, kind, i):
    s = dims(config)
    return {name: _draw_leaf(key, i, name, shape, s)
            for name, shape in leaf_shapes(config, kind).items()}


def top_weights(key, config):
    s = dims(config)
    return {name: _draw_leaf(key, s["L"], name,
                             tuple(s[d] for d in shape), s)
            for name, shape in TOP_SHAPES.items()}


# leaf of the program's stored tree -> leaf here
_PROGRAM_ATTN = {"norm_in": "input_layernorm", "w_qa": "q_a_proj",
                 "q_norm": "q_a_layernorm", "w_qb": "q_b_proj",
                 "w_kva": "kv_a_proj_with_mqa", "kv_norm": "kv_a_layernorm",
                 "w_kvb": "kv_b_proj", "w_o": "o_proj",
                 "norm_ff": "post_attention_layernorm"}
_PROGRAM_DENSE = {"gate": "gate_proj", "up": "up_proj", "down": "down_proj"}
_PROGRAM_MOE = {"router": "gate", "router_bias": "e_score_correction_bias",
                "shared_gate": "shared_gate_proj",
                "shared_up": "shared_up_proj",
                "shared_down": "shared_down_proj",
                "w_down": "experts_down_proj"}


def program_weights(seed, config, dtype):
    """The same draws in the program's stored tree (``models/kimi_k2.py:
    leaf_shapes``): attention leaves stacked over all layers, the dense
    MLPs over the dense layers, the expert layers' leaves over the expert
    layers with each expert's gate and up side by side. A stacked leaf is
    filled a layer at a time into one donated buffer, so no float32 copy of
    more than one layer's leaf is ever held."""
    key = _key(seed)
    s = dims(config)
    kinds = layer_kinds(config)
    every = list(range(s["L"]))
    dense = [i for i, k in enumerate(kinds) if k == "dense"]
    moe = [i for i, k in enumerate(kinds) if k == "moe"]

    def stacked(names, kind, layers):
        """``names``: the leaf here, or several joined on the last axis."""
        shapes = leaf_shapes(config, kind)
        held = jnp.float32 if names[0] in F32_LEAVES else dtype

        def one(key, i):
            return jnp.concatenate(
                [_draw_leaf(key, i, n, shapes[n], s) for n in names],
                axis=-1).astype(held)

        shape = jax.eval_shape(one, key, jnp.int32(0)).shape
        fill = jax.jit(lambda buf, key, at, i: buf.at[at].set(one(key, i)),
                       donate_argnums=0)
        buf = jnp.zeros((len(layers),) + shape, held)
        for at, i in enumerate(layers):
            buf = fill(buf, key, jnp.int32(at), jnp.int32(i))
        return buf

    top = jax.jit(lambda key: top_weights(key, config))(key)
    return {
        "embed": top["embed_tokens"].astype(dtype),
        "final_norm": top["norm"], "head": top["lm_head"].astype(dtype),
        "attn": {p: stacked((n,), "moe", every)
                 for p, n in _PROGRAM_ATTN.items()},
        "dense": {p: stacked((n,), "dense", dense)
                  for p, n in _PROGRAM_DENSE.items()},
        "moe": {**{p: stacked((n,), "moe", moe)
                   for p, n in _PROGRAM_MOE.items()},
                "w_gate_up": stacked(("experts_gate_proj",
                                      "experts_up_proj"), "moe", moe)}}


# ---------------------------------------------------------------------------
# the program under test
# ---------------------------------------------------------------------------

class ServeProgram:
    """``DecodeEngine`` -> ``warmup`` -> ``Scheduler`` -> ``EngineLoop``,
    driven in process: the entry points the other serving cells use."""

    def __init__(self, config, devices, seed):
        from paddle_tpu import serving
        from paddle_tpu.models.kimi_k2 import KimiK2Config
        from paddle_tpu.serving.server import EngineLoop

        sv = config["serving"]
        engine_kw = dict(sv["engine"])
        if "prefill_buckets" in engine_kw:
            engine_kw["prefill_buckets"] = tuple(engine_kw["prefill_buckets"])
        s = dims(config)
        self.cfg = KimiK2Config(
            vocab_size=s["V"], hidden_size=s["D"],
            intermediate_size=s["Fd"], moe_intermediate_size=s["F"],
            num_hidden_layers=s["L"], first_k_dense_replace=s["Ld"],
            num_attention_heads=s["H"], q_lora_rank=s["Rq"],
            kv_lora_rank=s["Rkv"], qk_nope_head_dim=s["nope"],
            qk_rope_head_dim=s["rope"], v_head_dim=s["dv"],
            n_routed_experts_published=s["E"], experts_held=s["G"],
            first_expert=s["first"], num_experts_per_tok=s["k"],
            n_shared_experts=int(config["n_shared_experts"]),
            routed_scaling_factor=s["scale"], rms_norm_eps=s["eps"],
            rope_theta=s["theta"], rope_scaling=config.get("rope_scaling"),
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


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1,
                                      keepdims=True) + eps) * g


def yarn_correction_range(config):
    """(low, high): the pair indices between which yarn's ramp runs."""
    s, y = dims(config), config["rope_scaling"]

    def turns_at(beta):
        return (s["rope"] * math.log(
            y["original_max_position_embeddings"] / (beta * 2 * math.pi))
            / (2 * math.log(s["theta"])))

    return (max(math.floor(turns_at(y["beta_fast"])), 0),
            min(math.ceil(turns_at(y["beta_slow"])), s["rope"] - 1))


def yarn_inv_freq(config):
    s, y = dims(config), config.get("rope_scaling")
    j = np.arange(s["rope"] // 2, dtype=np.float64)
    extra = s["theta"] ** (-2.0 * j / s["rope"])
    if not y:
        return extra.astype(np.float32)
    low, high = yarn_correction_range(config)
    ramp = np.clip((j - low) / ((high - low) or 0.001), 0.0, 1.0)
    return (extra / y["factor"] * ramp + extra * (1 - ramp)).astype(
        np.float32)


def softmax_scale(config):
    s, y = dims(config), config.get("rope_scaling")
    scale = (s["nope"] + s["rope"]) ** -0.5
    if y and y.get("mscale_all_dim"):
        m = 0.1 * y["mscale_all_dim"] * math.log(y["factor"]) + 1.0
        scale *= m * m
    return scale


def _rotate_interleaved(x, cos, sin):
    """The source's pairing: channels ``(2j, 2j + 1)`` turn together."""
    pairs = x.reshape(x.shape[:-1] + (-1, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape)


def _attention(x, w, s, mm, inv_freq, scale):
    """x [T, D] -> [T, D]: latent attention, expanded, causal."""
    T, H = x.shape[0], s["H"]
    hi = jax.lax.Precision.HIGHEST
    u = _rms(x, w["input_layernorm"], s["eps"])
    cq = _rms(mm(u, w["q_a_proj"]), w["q_a_layernorm"], s["eps"])
    q = mm(cq, w["q_b_proj"]).reshape(T, H, s["nope"] + s["rope"])
    kva = mm(u, w["kv_a_proj_with_mqa"])
    ckv = _rms(kva[:, :s["Rkv"]], w["kv_a_layernorm"], s["eps"])
    kv = mm(ckv, w["kv_b_proj"]).reshape(T, H, s["nope"] + s["dv"])
    angle = jnp.arange(T, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    q_rope = _rotate_interleaved(q[..., s["nope"]:], cos[:, None],
                                 sin[:, None])
    k_rope = _rotate_interleaved(kva[:, s["Rkv"]:], cos, sin)
    q_nope, k_nope, v = q[..., :s["nope"]], kv[..., :s["nope"]], \
        kv[..., s["nope"]:]
    blk = QUERY_BLOCK if T % QUERY_BLOCK == 0 else T

    def block(i):
        qn = jax.lax.dynamic_slice_in_dim(q_nope, i * blk, blk, 0)
        qr = jax.lax.dynamic_slice_in_dim(q_rope, i * blk, blk, 0)
        sc = (jnp.einsum("qhd,khd->hqk", qn, k_nope, precision=hi)
              + jnp.einsum("qhd,kd->hqk", qr, k_rope, precision=hi)) * scale
        mask = (jnp.arange(T)[None, :]
                <= i * blk + jnp.arange(blk)[:, None])[None]
        p = jax.nn.softmax(jnp.where(mask, sc, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=hi)

    att = jax.lax.map(block, jnp.arange(T // blk)).reshape(T, H * s["dv"])
    return x + mm(att, w["o_proj"])


def _gated(u, gate, up, down, mm):
    return mm(jax.nn.silu(mm(u, gate)) * mm(u, up), down)


def route(u, w, s):
    """u [T, D] -> (experts [T, k], weights [T, k]): float32 throughout,
    whatever the projections' precision: the source computes it so."""
    hi = jax.lax.Precision.HIGHEST
    score = jax.nn.sigmoid(jnp.matmul(u, w["gate"], precision=hi))
    _, experts = jax.lax.top_k(score + w["e_score_correction_bias"], s["k"])
    weights = jnp.take_along_axis(score, experts, axis=1)
    return experts, (weights / jnp.sum(weights, axis=1, keepdims=True)
                     * s["scale"])


def _moe_ffn(h, w, s, mm):
    """The chip's share: every held expert over every token, weighted by
    what the router gave it there (0 where it was not chosen), plus the
    shared expert. Returns (h + y, [T, G] which held experts a token
    chose)."""
    u = _rms(h, w["post_attention_layernorm"], s["eps"])
    experts, weights = route(u, w, s)

    def one(y, xs):
        g, gate, up, down = xs
        w_g = jnp.sum(jnp.where(experts == s["first"] + g, weights, 0.0),
                      axis=1)
        return y + w_g[:, None] * _gated(u, gate, up, down, mm), w_g > 0

    y, chose = jax.lax.scan(
        one, jnp.zeros_like(h),
        (jnp.arange(s["G"]), w["experts_gate_proj"], w["experts_up_proj"],
         w["experts_down_proj"]))
    shared = _gated(u, w["shared_gate_proj"], w["shared_up_proj"],
                    w["shared_down_proj"], mm)
    return h + y + shared, chose.T


def _dense_layer(x, w, s, mm, inv_freq, scale):
    h = _attention(x, w, s, mm, inv_freq, scale)
    u = _rms(h, w["post_attention_layernorm"], s["eps"])
    return (h + _gated(u, w["gate_proj"], w["up_proj"], w["down_proj"], mm),
            jnp.zeros((x.shape[0], s["G"]), bool))


def _moe_layer(x, w, s, mm, inv_freq, scale):
    return _moe_ffn(_attention(x, w, s, mm, inv_freq, scale), w, s, mm)


_LAYERS = {"dense": _dense_layer, "moe": _moe_layer}


def forward(config, seed, tokens, held="f32", compute="f32"):
    """tokens [T] -> logits [T, V], float32: the whole share on one
    sequence, layer by layer (the CPU tests' plain forward pass)."""
    hidden = _hidden(config, seed, [np.asarray(tokens, np.int32)[None]],
                     held, compute)[0][0][0]
    return _head(config, seed, held, compute)(hidden)


_SHAPE_KEYS = ("hidden_size", "num_attention_heads", "num_hidden_layers",
               "first_k_dense_replace", "intermediate_size",
               "moe_intermediate_size", "n_shared_experts",
               "n_routed_experts", "first_expert", "num_experts_per_tok",
               "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
               "qk_rope_head_dim", "v_head_dim", "vocab_size",
               "rms_norm_eps", "rope_theta", "routed_scaling_factor")


def _shape_of(config):
    """The keys that shape the programs, hashable."""
    return (tuple((k, config.get(k)) for k in _SHAPE_KEYS)
            + (("published_experts", config["published"]["n_routed_experts"]),
               ("rope_scaling", tuple(sorted(
                   (config.get("rope_scaling") or {}).items())))))


def _config_of(shape):
    config = dict(shape)
    config["published"] = {"n_routed_experts": config.pop(
        "published_experts")}
    config["rope_scaling"] = dict(config["rope_scaling"]) or None
    return config


@functools.lru_cache(maxsize=None)
def _programs(shape, held, compute):
    """The reference's jitted pieces for one configuration and precision;
    the key of the weights is an argument of each draw."""
    config = _config_of(shape)
    s = dims(config)
    mm = _mm(compute)
    inv_freq = jnp.asarray(yarn_inv_freq(config))
    scale = softmax_scale(config)

    def logits(top, hidden):
        return mm(_rms(hidden, top["norm"], s["eps"]), top["lm_head"])

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
                lambda row: layer(row, w, s, mm, inv_freq, scale))(x))
    return out


def _hidden(config, seed, blocks, held, compute):
    """Every block of token rows ``[n, T]`` through embedding and all the
    layers, one layer's weights on the device at a time. Returns (the
    blocks' hidden states ``[n, T, D]`` before the final norm, per layer
    the blocks' ``[n, T, G]`` held experts chosen)."""
    key = _key(seed)
    fns = _programs(_shape_of(config), held, compute)
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
    """As ``jamba._reference_serve``: for each sample ``(prompt, served)``
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
