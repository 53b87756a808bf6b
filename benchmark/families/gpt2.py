"""The GPT-2 family: learned positions, LayerNorm, tanh-GELU, biases.

Two halves that share nothing but the seeded weights:

* ``build`` hands the weights to the program under test
  (``paddle_tpu.models.gpt`` through ``make_train_step`` or
  ``DecodeEngine`` -> ``Scheduler`` -> ``EngineLoop``) and returns the
  object the timed window drives;
* ``reference`` is the plain model: forward pass, loss, gradients and the
  AdamW step in straightforward ``jax.numpy``, float32,
  ``precision=HIGHEST``, no kernels, no cache. It imports nothing of the
  program. The same functions at a lower ``precision`` are the control that
  ``correct`` has to refuse.

Weights are made on the device in one jitted call from the seed. Both
halves draw the same float32 values; the program gets them in its own
layout and in the type it serves or trains in.

Departures from the published model, all the program's (PERF.md lists them):
``lm_head`` is a matrix of its own where the published model ties it to
``wte``; the block decays every stacked leaf, LayerNorm gains and biases of
the blocks included (``decay_exempt`` names the leaves it leaves alone).
"""
import gc
import math

import jax
import jax.numpy as jnp
import numpy as np

MODES = ("train", "serve")
# the configuration keys that are widths: ``reduced`` may name none of them
WIDTH_KEYS = ("n_embd", "n_inner", "n_head")
LN_EPS = 1e-5
# leaves stacked on a leading layer axis, in the published names
BLOCK_LEAVES = ("ln_1_g", "ln_1_b", "c_attn_w", "c_attn_b", "c_proj_w",
                "c_proj_b", "ln_2_g", "ln_2_b", "c_fc_w", "c_fc_b",
                "mlp_proj_w", "mlp_proj_b")
# leaf of the program's tree -> leaf here (a reshape apart)
_PROGRAM_BLOCK = {"ln1_scale": "ln_1_g", "ln1_bias": "ln_1_b",
                  "w_qkv": "c_attn_w", "b_qkv": "c_attn_b",
                  "w_proj": "c_proj_w", "b_proj": "c_proj_b",
                  "ln2_scale": "ln_2_g", "ln2_bias": "ln_2_b",
                  "w_fc": "c_fc_w", "b_fc": "c_fc_b",
                  "w_out": "mlp_proj_w", "b_out": "mlp_proj_b"}
_PROGRAM_TOP = {"wte": "wte", "wpe": "wpe", "lm_head": "lm_head",
                "ln_f_scale": "ln_f_g", "ln_f_bias": "ln_f_b"}


# ---------------------------------------------------------------------------
# sizes and counts (from the configuration file's published keys)
# ---------------------------------------------------------------------------

def dims(config):
    d, h = int(config["n_embd"]), int(config["n_head"])
    if d % h:
        raise ValueError(f"n_embd {d} is not a multiple of n_head {h}")
    return {"L": int(config["n_layer"]), "D": d, "H": h, "hd": d // h,
            "F": int(config["n_inner"]), "V": int(config["vocab_size"]),
            "P": int(config["n_positions"])}


def leaf_shapes(config):
    s = dims(config)
    L, D, F, V, P = s["L"], s["D"], s["F"], s["V"], s["P"]
    return {"wte": (V, D), "wpe": (P, D), "lm_head": (D, V),
            "ln_f_g": (D,), "ln_f_b": (D,),
            "ln_1_g": (L, D), "ln_1_b": (L, D),
            "c_attn_w": (L, D, 3 * D), "c_attn_b": (L, 3 * D),
            "c_proj_w": (L, D, D), "c_proj_b": (L, D),
            "ln_2_g": (L, D), "ln_2_b": (L, D),
            "c_fc_w": (L, D, F), "c_fc_b": (L, F),
            "mlp_proj_w": (L, F, D), "mlp_proj_b": (L, D)}


def param_count(config):
    return sum(int(np.prod(s)) for s in leaf_shapes(config).values())


def matmul_param_count(config):
    """Parameters that are multiplied as matrices for every token: the
    blocks' four weights and ``lm_head``. ``wte``/``wpe`` are looked up."""
    shapes = leaf_shapes(config)
    return sum(int(np.prod(shapes[k])) for k in
               ("c_attn_w", "c_proj_w", "c_fc_w", "mlp_proj_w", "lm_head"))


def attention_flops_per_token(config, seq_len):
    """Forward QK^T and AV of causal attention for one token, averaged
    over a sequence of ``seq_len``: half of the full square,
    2 * (2 * D * T) / 2 a layer."""
    s = dims(config)
    return s["L"] * 2 * s["D"] * seq_len


def flops_per_token(config, seq_len):
    """Operations training requires per token, forward and backward:
    6 per multiplied parameter, and three times the forward attention.
    Recomputation is not counted."""
    return (6 * matmul_param_count(config)
            + 3 * attention_flops_per_token(config, seq_len))


def bytes_per_decode_step(config, live_lengths, weight_bytes=2,
                          cache_bytes=2):
    """Bytes one decode tick has to read: every multiplied weight once,
    and the keys and values of each live slot up to its length."""
    s = dims(config)
    weights = matmul_param_count(config) * weight_bytes
    cache = sum(int(n) for n in live_lengths) * s["L"] * 2 * s["D"] \
        * cache_bytes
    return weights + cache


# ---------------------------------------------------------------------------
# seeded weights
# ---------------------------------------------------------------------------

def _key(seed):
    seed = int(seed) % (1 << 62)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def _draw(key, config):
    """Every leaf in float32, published names. Traced inside a jit; the
    key is an argument, so that one compiled program serves every seed."""
    shapes = leaf_shapes(config)
    L = dims(config)["L"]
    std = 0.02
    resid = std / math.sqrt(2 * L)
    keys = dict(zip(sorted(shapes), jax.random.split(key, len(shapes))))
    out = {}
    for name, shape in shapes.items():
        z = jax.random.normal(keys[name], shape, jnp.float32)
        if name in ("c_proj_w", "mlp_proj_w"):
            out[name] = z * resid
        elif name.endswith("_g"):
            out[name] = 1.0 + z * std
        else:
            out[name] = z * std
    return out


def init_weights(seed, config):
    """The reference's weights: float32, published names, on the default
    device."""
    return jax.jit(lambda key: _draw(key, config))(_key(seed))


def _to_program(w, config, dtype):
    s = dims(config)
    L, D, H, hd = s["L"], s["D"], s["H"], s["hd"]
    reshape = {"w_qkv": (L, D, 3, H, hd), "b_qkv": (L, 3, H, hd),
               "w_proj": (L, H, hd, D)}
    tree = {k: w[v].astype(dtype) for k, v in _PROGRAM_TOP.items()}
    tree["blocks"] = {
        k: w[v].reshape(reshape.get(k, w[v].shape)).astype(dtype)
        for k, v in _PROGRAM_BLOCK.items()}
    return tree


def program_weights(seed, config, dtype, sharding=None):
    """The same draws in the program's tree and ``dtype``: one jitted call,
    nothing on the host, no float32 copy left behind."""
    fn = jax.jit(
        lambda key: _to_program(_draw(key, config), config, dtype),
        out_shardings=sharding)
    return fn(_key(seed))


def compared_part(name, x):
    """The part of leaf ``name`` whose norm is compared: all of it, but
    ``c_attn_b`` ([L, 3, ...] in either layout) without its key third. A
    key bias shifts every score of a row alike and softmax takes the shift
    out, so its gradient is zero in exact arithmetic: Adam then divides
    rounding noise by its own size, and that slice moved by a tenth more
    in the program than in the reference in every sound run (my chip runs,
    PR 25), which would have hidden any fault under 10 % in the leaf."""
    if name == "c_attn_b":
        return x.reshape(x.shape[0], 3, -1)[:, ::2]
    return x


def _leaf_norms_program(tree):
    """Per-leaf 2-norms of a program-layout tree under the published
    names (a reshape does not change a norm)."""
    def norm(name, x):
        x = compared_part(name, x.astype(jnp.float32))
        return jnp.sqrt(jnp.sum(jnp.square(x)))
    out = {v: norm(v, tree[k]) for k, v in _PROGRAM_TOP.items()}
    out.update({v: norm(v, tree["blocks"][k])
                for k, v in _PROGRAM_BLOCK.items()})
    return out


# ---------------------------------------------------------------------------
# the program under test
# ---------------------------------------------------------------------------

def _gpt_config(config, options, dtype):
    from paddle_tpu.models.gpt import GPTConfig

    s = dims(config)
    return GPTConfig(
        vocab_size=s["V"], max_seq_len=s["P"], num_layers=s["L"],
        num_heads=s["H"], d_model=s["D"], d_ff=s["F"], dtype=dtype,
        **options)


class TrainProgram:
    """``make_train_step`` on a mesh of the cell's devices with its state.
    The window drives ``step``; the three norms are read from the state
    this same object carries."""

    def __init__(self, config, devices, seed):
        from paddle_tpu.parallel import parallelize as PZ

        tr = config["training"]
        self.config, self.seed = config, seed
        self.tr = tr
        compute = jnp.dtype(tr["compute_dtype"])
        self.cfg = _gpt_config(config, tr["model_options"], compute)
        self.pcfg = PZ.ParallelConfig(**tr["parallel"])
        if self.pcfg.n_devices != len(devices):
            raise ValueError(
                f"parallel {tr['parallel']} wants {self.pcfg.n_devices} "
                f"devices, the cell has {len(devices)}")
        self.mesh = PZ.build_mesh(self.pcfg, devices=devices)
        self._sharding = self._param_sharding()
        self.params = program_weights(seed, config, jnp.float32,
                                      self._sharding)
        opt_sh = {"m": self._sharding, "v": self._sharding, "step": None}
        self.opt = jax.jit(
            lambda p: PZ.init_adamw_state(
                p, moment_dtype=jnp.dtype(tr["moment_dtype"])),
            out_shardings=opt_sh)(self.params)
        self._step = PZ.make_train_step(
            self.cfg, self.pcfg, self.mesh, lr=tr["lr"],
            weight_decay=tr["weight_decay"], grad_clip=tr["grad_clip"])
        self._norms = jax.jit(_leaf_norms_program)
        self._delta = jax.jit(lambda a, b: _leaf_norms_program(
            jax.tree_util.tree_map(lambda x, y: x - y, a, b)))

    def _param_sharding(self):
        from jax.sharding import NamedSharding, PartitionSpec

        from paddle_tpu.models import gpt as G

        specs = G.param_specs(self.cfg, pp=self.pcfg.axis_names[1],
                              tp=self.pcfg.axis_names[2])
        return jax.tree_util.tree_map(
            lambda sp: NamedSharding(self.mesh, sp), specs,
            is_leaf=lambda x: isinstance(x, PartitionSpec))

    def step(self, tokens, labels):
        """One optimizer step on ``[1, batch, T]`` int32 arrays; returns
        the loss, still on the device."""
        self.params, self.opt, loss, _ = self._step(
            self.params, self.opt, tokens, labels)
        return loss

    def first_grad_norms(self):
        """Per-leaf norm of the first gradient as the optimizer got it,
        worked out from the state after one step: m1 = (1 - b1) * g."""
        if int(self.opt["step"]) != 1:
            raise RuntimeError("read the first gradient after one step")
        scale = 1.0 / (1.0 - self.tr["adam_b1"])
        return {k: float(v) * scale
                for k, v in self._norms(self.opt["m"]).items()}

    def change_norms(self):
        """Per-leaf norm of parameters now minus the seeded ones, which
        are drawn again for the purpose and dropped."""
        start = program_weights(self.seed, self.config, jnp.float32,
                                self._sharding)
        out = {k: float(v)
               for k, v in self._delta(self.params, start).items()}
        del start
        return out

    def free(self):
        self.params = self.opt = self._step = None
        _free_device_memory()


class ServeProgram:
    """``DecodeEngine`` -> ``warmup`` -> ``Scheduler`` -> ``EngineLoop``,
    driven in process."""

    def __init__(self, config, devices, seed):
        from paddle_tpu import serving
        from paddle_tpu.serving.server import EngineLoop

        sv = config["serving"]
        engine_kw = dict(sv["engine"])
        # the KV pool's sizes sit at the top of the file, where ``reduced``
        # names them (None: the program's default)
        engine_kw.update({k: config[k] for k in ("num_pages",
                                                 "prefix_cache_pages")
                          if config.get(k) is not None})
        compute = jnp.dtype(sv["compute_dtype"])
        self.cfg = _gpt_config(config, sv.get("model_options", {}), compute)
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
        """Let go of weights, cache and executables, whoever still holds
        the engine object (a scheduler, a span wrapper)."""
        self.loop.stop()
        eng = self.engine
        eng.qparams = eng.cache.k = eng.cache.v = None
        eng._exec.clear()
        self.engine = self.scheduler = self.loop = None
        _free_device_memory()


def build(config, mode, devices, seed):
    if mode == "train":
        return TrainProgram(config, devices, seed)
    if mode == "serve":
        return ServeProgram(config, devices, seed)
    raise ValueError(f"mode {mode!r}: expected one of {MODES}")


def _free_device_memory():
    gc.collect()
    jax.clear_caches()


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------

def _scaled_cast(x, dtype, top):
    """``x`` as ``dtype`` holds it when scaled so that its largest value
    sits at the format's ``top``, back in float32."""
    scale = top / (jnp.max(jnp.abs(x)) + 1e-30)
    return (x * scale).astype(dtype).astype(jnp.float32) / scale


@jax.custom_vjp
def _mm_fp8(x, w):
    """x [T, K] @ w [K, N] as an fp8 training path computes it: operands in
    e4m3 forward, the incoming gradient in e5m2 backward, float32 sums."""
    return _mm_fp8_fwd(x, w)[0]


def _mm_fp8_fwd(x, w):
    qx = _scaled_cast(x, jnp.float8_e4m3fn, 448.0)
    qw = _scaled_cast(w, jnp.float8_e4m3fn, 448.0)
    hi = jax.lax.Precision.HIGHEST
    return jnp.matmul(qx, qw, precision=hi), (qx, qw)


def _mm_fp8_bwd(saved, g):
    qx, qw = saved
    qg = _scaled_cast(g, jnp.float8_e5m2, 57344.0)
    hi = jax.lax.Precision.HIGHEST
    return (jnp.matmul(qg, qw.T, precision=hi),
            jnp.matmul(qx.T, qg, precision=hi))


_mm_fp8.defvjp(_mm_fp8_fwd, _mm_fp8_bwd)


def round_weights(w, precision):
    """Every leaf as a weight-only format would hold it: ``bf16w`` rounds
    to bfloat16; ``int8w`` and ``fp8w`` (e4m3) to 8 bits with one scale per
    256 consecutive values, the granularity of the engine's own quantiser.
    Activations and arithmetic stay float32. In place, leaf by leaf: each
    float32 leaf is let go as it is rounded, so two whole models are never
    on the device. Returns ``w``."""
    def bf16(x):
        return x.astype(jnp.bfloat16).astype(jnp.float32)

    def chunked(to_8_bits, top):
        def one(x):
            flat = x.reshape(-1)
            pad = (-flat.shape[0]) % 256
            rows = jnp.pad(flat, (0, pad)).reshape(-1, 256)
            scale = jnp.max(jnp.abs(rows), axis=1, keepdims=True) / top
            q = to_8_bits(rows / jnp.where(scale > 0, scale, 1.0)) * scale
            return q.reshape(-1)[:flat.shape[0]].reshape(x.shape)
        return one

    def e4m3(r):
        # by arithmetic, not by a cast: 4 significant bits above 2**-6,
        # steps of 2**-9 below. (A lone float32 -> float8 -> float32 round
        # trip came back unrounded from the v5e's compiler: the control
        # then read a gap of exactly 0, my chip run, PR 25.)
        m, e = jnp.frexp(r)
        e = jnp.maximum(e, -5)
        return jnp.round(r * jnp.exp2(4.0 - e)) * jnp.exp2(e - 4.0)

    formats = {"bf16w": bf16, "int8w": chunked(jnp.round, 127.0),
               "fp8w": chunked(e4m3, 448.0)}
    if precision not in formats:
        raise ValueError(f"weight precision {precision!r}")
    one = jax.jit(formats[precision])
    for k in list(w):
        w[k] = one(w[k])
    return w


def _mm(precision):
    """The matrix product of the projections at ``precision``: ``f32``;
    ``bf16`` (operands rounded, float32 sums); ``fp8`` (above)."""
    hi = jax.lax.Precision.HIGHEST
    if precision == "f32":
        return lambda x, w: jnp.matmul(x, w, precision=hi)
    if precision == "bf16":
        return lambda x, w: jnp.matmul(
            x.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
            preferred_element_type=jnp.float32)
    if precision == "fp8":
        return _mm_fp8
    raise ValueError(f"precision {precision!r}")


def _layer_norm(x, g, b):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + LN_EPS) * g + b


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _block(x, w, n_head, mm):
    """One block on one sequence: x [T, D], w this layer's leaves."""
    T, D = x.shape
    hd = D // n_head
    h = _layer_norm(x, w["ln_1_g"], w["ln_1_b"])
    qkv = mm(h, w["c_attn_w"]) + w["c_attn_b"]
    q, k, v = (a.reshape(T, n_head, hd) for a in jnp.split(qkv, 3, axis=-1))
    hi = jax.lax.Precision.HIGHEST
    scores = jnp.einsum("qhd,khd->hqk", q, k, precision=hi) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((T, T), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    a = jnp.einsum("hqk,khd->qhd", probs, v, precision=hi).reshape(T, D)
    x = x + mm(a, w["c_proj_w"]) + w["c_proj_b"]
    h = _layer_norm(x, w["ln_2_g"], w["ln_2_b"])
    h = _gelu_tanh(mm(h, w["c_fc_w"]) + w["c_fc_b"])
    return x + mm(h, w["mlp_proj_w"]) + w["mlp_proj_b"]


def hidden_states(w, tokens, n_head, precision="f32", remat=False):
    """tokens [T] -> the last block's output [T, D], before ``ln_f``."""
    mm = _mm(precision)
    x = w["wte"][tokens] + w["wpe"][:tokens.shape[0]]
    blocks = {k: w[k] for k in BLOCK_LEAVES}

    def body(x, wl):
        return _block(x, wl, n_head, mm), None

    if remat:
        body = jax.checkpoint(body)
    x, _ = jax.lax.scan(body, x, blocks)
    return x


def logits_at(w, hidden, precision="f32"):
    return _mm(precision)(_layer_norm(hidden, w["ln_f_g"], w["ln_f_b"]),
                          w["lm_head"])


def forward(w, tokens, n_head, precision="f32"):
    """tokens [T] -> logits [T, V]."""
    return logits_at(w, hidden_states(w, tokens, n_head, precision), precision)


def sequence_loss_sum(w, tokens, labels, n_head, precision="f32",
                      remat=False):
    """Summed next-token cross-entropy of one sequence."""
    logits = logits_at(w, hidden_states(w, tokens, n_head, precision,
                                        remat), precision)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, labels[:, None], axis=-1))


def _reference_train(config, seed, batches, precision):
    """Follow the first ``len(batches)`` optimizer steps. Gradients are
    accumulated sequence by sequence so that the float32 model fits beside
    its state. Returns what the program is compared on."""
    tr = config["training"]
    n_head = dims(config)["H"]
    lr, wd, clip = tr["lr"], tr["weight_decay"], tr["grad_clip"]
    b1, b2, eps = tr["adam_b1"], tr["adam_b2"], tr["adam_eps"]
    exempt = set(tr["decay_exempt"])

    @jax.jit
    def grad_one(w, tokens, labels):
        return jax.value_and_grad(sequence_loss_sum)(
            w, tokens, labels, n_head, precision, True)

    @jax.jit
    def add(acc, g):
        return jax.tree_util.tree_map(jnp.add, acc, g)

    @jax.jit
    def adamw(w, g, m, v, step, n_tokens):
        g = {k: x / n_tokens for k, x in g.items()}
        gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in g.values()))
        scale = jnp.minimum(1.0, clip / (gnorm + 1e-6)) \
            if clip is not None else 1.0
        g = {k: x * scale for k, x in g.items()}
        c1, c2 = 1 - b1 ** step, 1 - b2 ** step
        new_w, new_m, new_v = {}, {}, {}
        for k in w:
            new_m[k] = b1 * m[k] + (1 - b1) * g[k]
            new_v[k] = b2 * v[k] + (1 - b2) * g[k] * g[k]
            u = (new_m[k] / c1) / (jnp.sqrt(new_v[k] / c2) + eps)
            decay = 0.0 if k in exempt else wd
            new_w[k] = w[k] - lr * (u + decay * w[k])
        gn = {k: jnp.sqrt(jnp.sum(jnp.square(compared_part(k, x))))
              for k, x in g.items()}
        return new_w, new_m, new_v, gn

    @jax.jit
    def change(a, b):
        return {k: jnp.sqrt(jnp.sum(jnp.square(
            compared_part(k, a[k] - b[k])))) for k in a}

    w = init_weights(seed, config)
    m = jax.tree_util.tree_map(jnp.zeros_like, w)
    v = jax.tree_util.tree_map(jnp.zeros_like, w)
    losses, first_grad = [], None
    for i, (tokens, labels) in enumerate(batches):
        tokens = np.asarray(tokens).reshape(-1, tokens.shape[-1])
        labels = np.asarray(labels).reshape(-1, labels.shape[-1])
        acc, total = None, 0.0
        for row_t, row_l in zip(tokens, labels):
            loss, g = grad_one(w, row_t, row_l)
            total += float(loss)
            acc = g if acc is None else add(acc, g)
        losses.append(total / tokens.size)
        w, m, v, gn = adamw(w, acc, m, v, float(i + 1), float(tokens.size))
        del acc
        if i == 0:
            first_grad = {k: float(x) for k, x in gn.items()}
    start = init_weights(seed, config)
    delta = {k: float(x) for k, x in change(w, start).items()}
    del w, m, v, start
    _free_device_memory()
    return {"losses": losses, "first_grad_norms": first_grad,
            "change_norms": delta}


def _reference_serve(config, seed, samples, pads, rows, columns,
                     chosen_by=()):
    """For each sample ``(prompt, served)``: one forward over prompt and
    served tokens, padded to the smallest of ``pads`` that holds them
    (causal, so the padding changes nothing before it). Returns
    {"gaps": {"served": [...]}, "logits": {"reference": [...]}}: for each
    sample, at every served position, the gap by which the served token's
    logit lies below the reference's best, and the reference's logits at
    the vocabulary ``columns``.

    ``chosen_by`` names controls, each ``<weights>`` (``round_weights``) or
    ``<weights>+<compute>`` (``_mm``): a control need not decode. At each
    position of the same prompts and tokens it gives the logits at
    ``columns`` and the token it puts first, with its weights held and its
    products computed in that precision; both come back under its name."""
    n_head = dims(config)["H"]
    pads = sorted(pads)
    columns = jnp.asarray(columns, jnp.int32)

    # ``columns`` is an argument, not a constant of the programs: it is
    # drawn from the seed, and a constant would compile anew for every seed
    def lower(compute):
        @jax.jit
        def fn(w, tokens, at, columns):
            h = hidden_states(w, tokens, n_head, compute)[at]
            logits = logits_at(w, h, compute)
            return jnp.argmax(logits, axis=-1), logits[:, columns]
        return fn

    @jax.jit
    def full(w, tokens, at, picked, columns):
        logits = logits_at(w, hidden_states(w, tokens, n_head)[at])
        best = jnp.max(logits, axis=-1)
        gaps = best[None] - jnp.take_along_axis(logits, picked.T, axis=-1).T
        return gaps, logits[:, columns]         # [names, rows], [rows, C]

    def padded(prompt, served):
        n, k = len(prompt), len(served)
        fit = [p for p in pads if p >= n + k]
        if not fit or k > rows:
            raise ValueError(f"sample of {n}+{k} tokens exceeds the "
                             f"reference's padding {pads[-1]}/{rows}")
        tokens = np.zeros((fit[0],), np.int32)
        tokens[:n + k] = list(prompt) + list(served)
        at = np.zeros((rows,), np.int32)
        at[:k] = np.arange(n - 1, n + k - 1)
        return tokens, at, k

    feeds = [padded(*sample) for sample in samples]
    chosen = {"served": [np.asarray(served, np.int32)
                         for _, served in samples]}
    logits = {}
    by_weights = {}
    for name in chosen_by:
        held, _, compute = name.partition("+")
        by_weights.setdefault(held, []).append((name, compute or "f32"))
    for held, controls in by_weights.items():   # one model at a time
        w = round_weights(init_weights(seed, config), held)
        for name, compute in controls:
            fn = lower(compute)
            got = [fn(w, tokens, at, columns) for tokens, at, _ in feeds]
            chosen[name] = [np.asarray(first)[:k]
                            for (first, _), (_, _, k) in zip(got, feeds)]
            logits[name] = [np.asarray(cols)[:k]
                            for (_, cols), (_, _, k) in zip(got, feeds)]
        del w, got
        _free_device_memory()
    w = init_weights(seed, config)
    names = list(chosen)
    gaps = {name: [] for name in names}
    logits["reference"] = []
    for i, (tokens, at, k) in enumerate(feeds):
        picked = np.zeros((len(names), rows), np.int32)
        for j, name in enumerate(names):
            picked[j, :k] = chosen[name][i]
        g, cols = full(w, tokens, at, picked, columns)
        g = np.asarray(g, np.float64)
        for j, name in enumerate(names):
            gaps[name].append(g[j, :k])
        logits["reference"].append(np.asarray(cols)[:k])
    del w
    _free_device_memory()
    return {"gaps": gaps, "logits": logits}


def reference(config, mode, seed, precision="f32", **kw):
    if mode == "train":
        return _reference_train(config, seed, kw["batches"], precision)
    if mode == "serve":
        return _reference_serve(config, seed, kw["samples"], kw["pads"],
                                kw["rows"], kw["columns"],
                                kw.get("chosen_by", ()))
    raise ValueError(f"mode {mode!r}: expected one of {MODES}")
