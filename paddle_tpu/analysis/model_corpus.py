"""Built-in model programs for the lint gate.

Every program family the framework ships is built here in a small
configuration and handed to the checkers: the CLI (``tools/paddle_lint.py
--all-models``) and the pytest gate (tests/test_static_analysis.py) both
demand zero error-severity findings on each of them, so any checker
regression or program-builder regression trips tier-1.

Builders construct under fresh ``Program``/``unique_name`` guards and
never execute anything — transpiled PS programs include
``listen_and_serv``/``send``/``recv`` host ops but no server is started.
"""
from __future__ import annotations

from typing import Callable, Dict, List, Optional, Sequence

__all__ = ["MODEL_BUILDERS", "build_model_program", "model_names",
           "ModelProgram"]


class ModelProgram:
    """One built program + the feed/fetch context the checkers need."""

    def __init__(self, name, main, startup=None, feed_names=(),
                 fetch_names=(), peer_programs=(), extra=None):
        self.name = name
        self.main = main
        self.startup = startup
        self.feed_names = list(feed_names)
        self.fetch_names = list(fetch_names)
        self.peer_programs = list(peer_programs)
        self.extra = extra or {}


def _fluid():
    import paddle_tpu as fluid

    return fluid


def _guarded(build):
    """Run a builder under fresh program + unique-name guards."""
    fluid = _fluid()
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 7
    with fluid.unique_name.guard():
        with fluid.program_guard(main, startup):
            out = build(fluid)
    return main, startup, out


# ---------------------------------------------------------------------------
# builders
# ---------------------------------------------------------------------------

def build_mlp() -> ModelProgram:
    def b(fluid):
        x = fluid.layers.data("x", [8], dtype="float32")
        y = fluid.layers.data("y", [1], dtype="int64")
        h = fluid.layers.fc(x, 32, act="relu")
        logits = fluid.layers.fc(h, 4)
        loss = fluid.layers.reduce_mean(
            fluid.layers.softmax_with_cross_entropy(logits, y))
        fluid.optimizer.SGD(0.1).minimize(loss)
        return loss

    main, startup, loss = _guarded(b)
    return ModelProgram("mlp", main, startup, ["x", "y"], [loss.name])


def build_gpt() -> ModelProgram:
    """Static-graph GPT-style LM head: embedding -> fc stack -> tied
    vocab projection -> softmax CE (the flagship decoder itself is the
    pure-JAX models/gpt.py; this is its fluid-program counterpart at lint
    scale)."""
    def b(fluid):
        V, T, D = 64, 8, 32
        tok = fluid.layers.data("tokens", [T], dtype="int64")
        lbl = fluid.layers.data("labels", [T, 1], dtype="int64")
        emb = fluid.layers.embedding(tok, size=[V, D],
                                     param_attr=fluid.ParamAttr("wte"))
        h = fluid.layers.fc(emb, D, num_flatten_dims=2, act="relu")
        h = fluid.layers.fc(h, D, num_flatten_dims=2, act="relu")
        logits = fluid.layers.fc(h, V, num_flatten_dims=2)
        loss = fluid.layers.reduce_mean(
            fluid.layers.softmax_with_cross_entropy(logits, lbl))
        fluid.optimizer.Adam(1e-3).minimize(loss)
        return loss

    main, startup, loss = _guarded(b)
    return ModelProgram("gpt", main, startup, ["tokens", "labels"],
                        [loss.name])


def build_ernie() -> ModelProgram:
    """The ERNIE program shape: the fluid transformer encoder classifier
    (models/transformer_encoder.py — the static counterpart of
    models/ernie.py)."""
    def b(fluid):
        from paddle_tpu.models.transformer_encoder import (
            transformer_encoder_classifier)

        V, T = 32, 8
        src = fluid.layers.data("src", [T], dtype="int64")
        pos = fluid.layers.data("pos", [T], dtype="int64")
        label = fluid.layers.data("label", [1], dtype="int64")
        loss, _logits = transformer_encoder_classifier(
            src, pos, label, vocab_size=V, max_pos=T, num_layers=2,
            num_heads=4, d_model=32, d_ff=64, num_classes=2)
        fluid.optimizer.Adam(2e-3).minimize(loss)
        return loss

    main, startup, loss = _guarded(b)
    return ModelProgram("ernie", main, startup, ["src", "pos", "label"],
                        [loss.name])


def build_resnet() -> ModelProgram:
    def b(fluid):
        from paddle_tpu.models.resnet import resnet

        img = fluid.layers.data("image", [3, 32, 32], dtype="float32")
        lbl = fluid.layers.data("label", [1], dtype="int64")
        logits = resnet(img, class_dim=10, depth=18)
        loss = fluid.layers.reduce_mean(
            fluid.layers.softmax_with_cross_entropy(logits, lbl))
        fluid.optimizer.Momentum(0.1, 0.9).minimize(loss)
        return loss

    main, startup, loss = _guarded(b)
    return ModelProgram("resnet", main, startup, ["image", "label"],
                        [loss.name])


def build_pipeline() -> ModelProgram:
    def b(fluid):
        x = fluid.layers.data("x", [8], dtype="float32")
        y = fluid.layers.data("y", [1], dtype="float32")
        h1 = fluid.layers.fc(x, 16, act="relu")
        h2 = fluid.layers.fc(h1, 16, act="relu")
        pred = fluid.layers.fc(h2, 1)
        loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
        fluid.optimizer.PipelineOptimizer(
            fluid.optimizer.SGD(0.05), num_stages=2,
            num_microbatches=2).minimize(loss)
        return loss

    main, startup, loss = _guarded(b)
    return ModelProgram("pipeline", main, startup, ["x", "y"], [loss.name])


def build_grad_merge() -> ModelProgram:
    def b(fluid):
        x = fluid.layers.data("x", [8], dtype="float32")
        y = fluid.layers.data("y", [1], dtype="int64")
        h = fluid.layers.fc(x, 16, act="relu")
        logits = fluid.layers.fc(h, 4)
        loss = fluid.layers.reduce_mean(
            fluid.layers.softmax_with_cross_entropy(logits, y))
        fluid.optimizer.GradientMergeOptimizer(
            fluid.optimizer.MomentumOptimizer(0.1, 0.9),
            k_steps=2).minimize(loss)
        return loss

    main, startup, loss = _guarded(b)
    return ModelProgram("grad_merge", main, startup, ["x", "y"],
                        [loss.name])


def build_ps_transpiled() -> ModelProgram:
    """DistributeTranspiler output: the trainer program (send/recv host
    ops) is the primary; the pserver program rides in ``extra`` and is
    linted separately by the gate."""
    from paddle_tpu.transpiler.distribute_transpiler import (
        DistributeTranspiler)

    def b(fluid):
        x = fluid.layers.data("x", [4], dtype="float32")
        y = fluid.layers.data("y", [1], dtype="float32")
        pred = fluid.layers.fc(x, 1)
        loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
        fluid.optimizer.SGDOptimizer(0.1).minimize(loss)
        return loss

    main, startup, loss = _guarded(b)
    t = DistributeTranspiler()
    t.transpile(trainer_id=0, program=main, pservers="127.0.0.1:0",
                trainers=1, sync_mode=True)
    trainer = t.get_trainer_program(wait_port=False)
    pserver = t.get_pserver_program("127.0.0.1:0")
    return ModelProgram("ps_transpiled", trainer, startup, ["x", "y"],
                        [loss.name], extra={"pserver": pserver})


def build_serving_prefill() -> ModelProgram:
    """The serving prefill program shape (docs/serving.md): a FIXED-length
    bucket slice of a decoder — tokens [1, T] in, last-position logits
    out. Every dim static (``append_batch_size=False``) on purpose: the
    recompile_risk checker should find NOTHING to flag, mirroring the
    zero-recompile contract the real engine (paddle_tpu/serving/engine.py)
    enforces at runtime."""
    def b(fluid):
        V, T, D = 64, 16, 32
        tok = fluid.layers.data("tokens", [1, T], dtype="int64",
                                append_batch_size=False)
        emb = fluid.layers.embedding(tok, size=[V, D],
                                     param_attr=fluid.ParamAttr("srv_wte"))
        h = fluid.layers.fc(emb, D, num_flatten_dims=2, act="relu")
        h = fluid.layers.fc(h, D, num_flatten_dims=2, act="relu")
        last = fluid.layers.slice(h, axes=[1], starts=[T - 1], ends=[T])
        logits = fluid.layers.fc(
            fluid.layers.reshape(last, [1, D]), V)
        return fluid.layers.softmax(logits)

    main, startup, prob = _guarded(b)
    return ModelProgram("serving_prefill", main, startup, ["tokens"],
                        [prob.name])


def build_serving_decode() -> ModelProgram:
    """The serving decode program shape: one token per slot over a static
    [max_batch] layout plus a fixed-shape cache feed that is shifted
    ring-buffer style and fetched back — the IR-level model of the
    donate-in/donate-out KV pools. Donation + recompile_risk are the
    checkers this program exists for: fixed shapes end to end, no
    persistable writes, the updated cache is an explicit fetch."""
    def b(fluid):
        V, B, S, D = 64, 4, 8, 32
        tok = fluid.layers.data("token", [B, 1], dtype="int64",
                                append_batch_size=False)
        cache = fluid.layers.data("cache_k", [B, S, D], dtype="float32",
                                  append_batch_size=False)
        emb = fluid.layers.embedding(tok, size=[V, D],
                                     param_attr=fluid.ParamAttr("srv_wte2"))
        # ring shift: drop the oldest cache row, append this token's row
        tail = fluid.layers.slice(cache, axes=[1], starts=[1], ends=[S])
        new_cache = fluid.layers.concat([tail, emb], axis=1)
        pooled = fluid.layers.reduce_mean(new_cache, dim=1)    # [B, D]
        logits = fluid.layers.fc(pooled, V)
        return fluid.layers.softmax(logits), new_cache

    main, startup, (prob, new_cache) = _guarded(b)
    return ModelProgram("serving_decode", main, startup,
                        ["token", "cache_k"],
                        [prob.name, new_cache.name])


def build_serving_prefill_tp2() -> ModelProgram:
    """The serving prefill shape under a Megatron tp=2 annotation set
    (ISSUE 13): first fc column-split, second fc row-split — the
    IR-level model of the tensor-parallel prefill executable
    (``EngineConfig(sharding="tp")``). Propagation must derive the
    column-split bias, record the row-parallel partial-sum as an info
    edge, and find ZERO errors — the static twin of the engine's
    tp-logits-match-single-chip parity bar."""
    from paddle_tpu import sharding

    mp = build_serving_prefill()
    params = {p.name for p in mp.main.all_parameters()}
    fc_w = sorted(p for p in params if p.endswith(".w_0"))
    sharding.annotate_program(
        mp.main,
        {"srv_wte": (), fc_w[0]: (None, "tp"), fc_w[1]: ("tp", None)},
        mesh_axes=[("tp", 2)])
    return ModelProgram("serving_prefill_tp2", mp.main, mp.startup,
                        mp.feed_names, mp.fetch_names)


def build_serving_decode_tp2() -> ModelProgram:
    """The serving decode shape with the KV-HEAD SPLIT the tp engine
    runs: the cache feed is [B, S, nh, hd] annotated ``tp`` on the head
    dim (exactly how the engine shards its pool at dim 3), the
    up-projection is column-split, the logits head row-split. The
    sharding checker must see the head split ride through the ring
    shift (slice+concat) and the pooled reduction with zero errors."""
    def b(fluid):
        V, B, S, NH, HD = 64, 4, 8, 4, 8
        D = NH * HD
        tok = fluid.layers.data("token", [B, 1], dtype="int64",
                                append_batch_size=False)
        cache = fluid.layers.data("cache_k", [B, S, NH, HD],
                                  dtype="float32",
                                  append_batch_size=False)
        emb = fluid.layers.embedding(
            tok, size=[V, D], param_attr=fluid.ParamAttr("srv_wte_tp"))
        h = fluid.layers.fc(emb, D, num_flatten_dims=2)     # column-par
        hr = fluid.layers.reshape(h, [B, 1, NH, HD])
        # ring shift on the head-split cache: drop the oldest row,
        # append this token's head-split row
        tail = fluid.layers.slice(cache, axes=[1], starts=[1], ends=[S])
        new_cache = fluid.layers.concat([tail, hr], axis=1)
        pooled = fluid.layers.reduce_mean(new_cache, dim=1)  # [B,NH,HD]
        flat = fluid.layers.reshape(pooled, [B, D])
        logits = fluid.layers.fc(flat, V)                    # row-par
        return fluid.layers.softmax(logits), new_cache

    from paddle_tpu import sharding

    main, startup, (prob, new_cache) = _guarded(b)
    params = {p.name for p in main.all_parameters()}
    fc_w = sorted(p for p in params if p.endswith(".w_0"))
    sharding.annotate_program(
        main,
        {"cache_k": (None, None, "tp", None),
         fc_w[0]: (None, "tp"), fc_w[1]: ("tp", None)},
        mesh_axes=[("tp", 2)])
    return ModelProgram("serving_decode_tp2", main, startup,
                        ["token", "cache_k"],
                        [prob.name, new_cache.name])


def build_mlp_dp() -> ModelProgram:
    """The mlp with GSPMD-style dp annotations (ISSUE 12): ONLY the two
    data feeds are annotated batch-sharded; propagation derives every
    activation/grad spec, weights replicate, and the loss reduction
    surfaces as the one implied psum edge — the sharding checker must
    find zero errors."""
    from paddle_tpu import sharding

    mp = build_mlp()
    sharding.annotate_program(
        mp.main, {"x": ("dp", None), "y": ("dp", None)},
        mesh_axes=[("dp", 8)], data_axis="dp")
    return ModelProgram("mlp_dp", mp.main, mp.startup, mp.feed_names,
                        mp.fetch_names)


def build_gpt_tp2() -> ModelProgram:
    """The fluid gpt with a Megatron tp=2 annotation set: embedding
    replicated, first fc column-split, second fc row-split — propagation
    derives the column-split bias, detects the partial-sum pair, and
    records the implied psum edge (info), with zero errors."""
    from paddle_tpu import sharding

    mp = build_gpt()
    sharding.annotate_program(
        mp.main,
        {"wte": (), "fc_0.w_0": (None, "tp"), "fc_1.w_0": ("tp", None)},
        mesh_axes=[("tp", 2)])
    return ModelProgram("gpt_tp2", mp.main, mp.startup, mp.feed_names,
                        mp.fetch_names)


def build_gpt_fsdp() -> ModelProgram:
    """The fluid gpt with fsdp-style annotations: every weight matrix
    (embedding included) sharded dim-0 over dp — propagation records the
    implied gathers (fsdp's all-gather-for-compute) as info edges, zero
    errors."""
    from paddle_tpu import sharding

    mp = build_gpt()
    mesh = [("dp", 8)]
    ann = {"wte": ("dp", None)}
    for p in mp.main.all_parameters():
        if p.ndim == 2 and p.name != "wte" and p.shape[0] % 8 == 0:
            ann[p.name] = ("dp", None)
    sharding.annotate_program(mp.main, ann, mesh_axes=mesh,
                              data_axis="dp")
    return ModelProgram("gpt_fsdp", mp.main, mp.startup, mp.feed_names,
                        mp.fetch_names)


MODEL_BUILDERS: "Dict[str, Callable[[], ModelProgram]]" = {
    "mlp": build_mlp,
    "gpt": build_gpt,
    "ernie": build_ernie,
    "resnet": build_resnet,
    "pipeline": build_pipeline,
    "grad_merge": build_grad_merge,
    "ps_transpiled": build_ps_transpiled,
    "serving_prefill": build_serving_prefill,
    "serving_decode": build_serving_decode,
    "serving_prefill_tp2": build_serving_prefill_tp2,
    "serving_decode_tp2": build_serving_decode_tp2,
    "mlp_dp": build_mlp_dp,
    "gpt_tp2": build_gpt_tp2,
    "gpt_fsdp": build_gpt_fsdp,
}


def model_names() -> List[str]:
    return sorted(MODEL_BUILDERS)


def build_model_program(name: str) -> ModelProgram:
    return MODEL_BUILDERS[name]()
