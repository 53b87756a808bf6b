"""DeepFM-style CTR training over the parameter-server path — the PaddleRec
north-star config (BASELINE.md: "PaddleRec DeepFM / Wide&Deep — distributed
PS path functional").

Mirrors the reference recipe end to end:
  MultiSlot data files -> QueueDataset (threaded feed) -> embedding
  (is_distributed -> distributed_lookup_table row pulls from the C++-backed
  sparse PS table) -> cvm (continuous_value_model) -> FM + DNN tower ->
  sigmoid CE -> DistributeTranspiler sync PS training with 2 real trainer
  processes; loss tracks the single-process local run.
"""
import multiprocessing
import os

import numpy as np

import paddle_tpu as fluid
from paddle_tpu.distributed import ParameterServer, PSClient
from paddle_tpu.transpiler.distribute_transpiler import DistributeTranspiler

VOCAB = 100
N_IDS = 3          # sparse ids per instance
EMB_DIM = 8
DENSE_DIM = 4
BATCH = 32


def _write_files(tmp_path, n_files=2, lines=64, seed=0):
    """MultiSlot lines: label(1f) show_click(2f) dense(4f) ids(3u).
    Click probability is driven by a planted id weight vector + dense weights
    so the model has real signal to learn."""
    rng = np.random.RandomState(seed)
    id_w = rng.randn(VOCAB) * 1.5
    d_w = rng.randn(DENSE_DIM)
    files = []
    for fi in range(n_files):
        path = os.path.join(str(tmp_path), f"ctr_{fi}.txt")
        with open(path, "w") as f:
            for _ in range(lines):
                ids = rng.randint(0, VOCAB, size=N_IDS)
                dense = rng.randn(DENSE_DIM)
                logit = id_w[ids].sum() * 0.5 + dense @ d_w
                label = 1.0 if 1.0 / (1 + np.exp(-logit)) > rng.rand() else 0.0
                show, click = 1.0, label
                toks = (["1", f"{label:.0f}", "2", f"{show:.1f}",
                         f"{click:.1f}", str(DENSE_DIM)]
                        + [f"{v:.4f}" for v in dense]
                        + [str(N_IDS)] + [str(i) for i in ids])
                f.write(" ".join(toks) + "\n")
        files.append(path)
    return files


def _build_ctr(seed=0, distributed=False):
    from paddle_tpu.framework import unique_name
    from paddle_tpu.framework.param_attr import ParamAttr
    from paddle_tpu.framework.initializer import ConstantInitializer

    prog, startup = fluid.Program(), fluid.Program()
    prog.random_seed = startup.random_seed = seed
    with unique_name.guard():
        with fluid.program_guard(prog, startup):
            label = fluid.layers.data("label", [1], dtype="float32")
            show_click = fluid.layers.data("show_click", [2], dtype="float32")
            dense = fluid.layers.data("dense", [DENSE_DIM], dtype="float32")
            ids = fluid.layers.data("ids", [N_IDS], dtype="int64")
            # zero init matches the PS sparse table's on-demand zero rows, so
            # the local baseline and the distributed run start identically
            emb = fluid.layers.embedding(
                ids, size=[VOCAB, EMB_DIM], is_sparse=True,
                is_distributed=distributed,
                param_attr=ParamAttr(name="ctr_emb",
                                     initializer=ConstantInitializer(0.0)))
            emb_sum = fluid.layers.reduce_sum(emb, dim=1)      # [B, D]
            fm = fluid.layers.reduce_sum(
                fluid.layers.square(emb_sum)
                - fluid.layers.reduce_sum(fluid.layers.square(emb), dim=1),
                dim=1, keep_dim=True)                          # [B, 1]
            x = fluid.layers.continuous_value_model(
                fluid.layers.concat([show_click, emb_sum], axis=1),
                show_click, use_cvm=True)
            feat = fluid.layers.concat([x, dense, fm], axis=1)
            h = fluid.layers.fc(feat, 16, act="relu")
            logit = fluid.layers.fc(h, 1)
            loss = fluid.layers.mean(
                fluid.layers.sigmoid_cross_entropy_with_logits(logit, label))
    return prog, startup, loss


def _make_dataset(files, prog):
    ds = fluid.DatasetFactory().create_dataset("QueueDataset")
    ds.set_batch_size(BATCH)
    ds.set_filelist(files)
    block = prog.global_block()
    ds.set_use_var([block.var("label"), block.var("show_click"),
                    block.var("dense"), block.var("ids")])
    return ds


def _feed_iter(files, prog, threads=2):
    from paddle_tpu.dataset import iter_batches_threaded
    ds = _make_dataset(files, prog)
    return iter_batches_threaded(ds, threads=threads)


def _run_local(files, epochs=6):
    prog, startup, loss = _build_ctr()
    with fluid.program_guard(prog, startup):
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    exe = fluid.Executor(fluid.XLAPlace(0))
    scope = fluid.Scope()
    exe.run(startup, scope=scope)
    losses = []
    for _ in range(epochs):
        for feed in _feed_iter(files, prog):
            out = exe.run(prog, feed=feed, fetch_list=[loss], scope=scope)
            losses.append(float(out[0]))
    return losses


def test_ctr_local_learns(tmp_path):
    files = _write_files(tmp_path)
    losses = _run_local(files)
    assert losses[-1] < losses[0] * 0.85, losses[:3] + losses[-3:]


def test_transpiled_ctr_program_shape(tmp_path):
    """The transpiled trainer program uses remote row pulls + sparse pushes
    for the embedding and keeps cvm on-device; the pserver program registers
    a sparse table for it."""
    prog, startup, loss = _build_ctr(distributed=True)
    with fluid.program_guard(prog, startup):
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    t = DistributeTranspiler()
    t.transpile(trainer_id=0, program=prog, pservers="127.0.0.1:0",
                trainers=2, sync_mode=True)
    tp = t.get_trainer_program()
    types = [op.type for op in tp.global_block().ops]
    assert "distributed_lookup_table" in types
    assert "distributed_push_sparse" in types
    assert "cvm" in types
    assert "lookup_table" not in types and "lookup_table_grad" not in types
    # dense send/recv never reference the sparse param
    for op in tp.global_block().ops:
        if op.type in ("send", "recv"):
            assert op.attrs.get("param") != "ctr_emb"
    ps = t.get_pserver_program("127.0.0.1:0")
    tables = ps.global_block().ops[0].attr("tables")
    sparse = [tb for tb in tables if tb.get("is_sparse")]
    assert sparse and sparse[0]["name"] == "ctr_emb" \
        and sparse[0]["dim"] == EMB_DIM


def _trainer_proc(trainer_id, endpoint, files, epochs, q):
    assert os.environ.get("JAX_PLATFORMS") == "cpu"
    import paddle_tpu as fluid  # noqa: F811 (fresh import in child)
    from paddle_tpu.transpiler.distribute_transpiler import DistributeTranspiler

    prog, startup, loss = _build_ctr(distributed=True)
    with fluid.program_guard(prog, startup):
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    t = DistributeTranspiler()
    t.transpile(trainer_id=trainer_id, program=prog, pservers=endpoint,
                trainers=2, sync_mode=True)
    tp = t.get_trainer_program()
    exe = fluid.Executor(fluid.XLAPlace(0))
    scope = fluid.Scope()
    exe.run(startup, scope=scope)
    losses = []
    for _ in range(epochs):
        for feed in _feed_iter(files, prog):
            out = exe.run(tp, feed=feed, fetch_list=[loss], scope=scope)
            losses.append(float(out[0]))
    from paddle_tpu.distributed import PSClient
    PSClient.instance(trainer_id).complete([endpoint])
    q.put((trainer_id, losses))


def test_two_trainer_ctr_cluster(tmp_path):
    """2 trainer processes, sync dense + async sparse pushes against one
    pserver: DeepFM converges and tracks the local single-process curve."""
    files = _write_files(tmp_path, n_files=2)
    epochs = 6
    local_losses = _run_local(files, epochs=epochs)

    server = ParameterServer("127.0.0.1:0", trainer_num=2, sync_mode=True)
    # dense tower params are registered on first push (ensure_init); the
    # sparse table must exist up front for the first pull
    server.register_sparse("ctr_emb", EMB_DIM, "sgd", lr=0.1)
    prog, startup, loss = _build_ctr(distributed=True)
    with fluid.program_guard(prog, startup):
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    t = DistributeTranspiler()
    t.transpile(trainer_id=0, program=prog, pservers="127.0.0.1:0",
                trainers=2, sync_mode=True)
    for tb in t.get_pserver_program("127.0.0.1:0").global_block() \
            .ops[0].attr("tables"):
        if not tb.get("is_sparse"):
            server.register_dense(tb["name"], tb["shape"], tb["optimizer"],
                                  tb["lr"], **tb.get("hparams", {}))
    server.start()

    old_env = {"JAX_PLATFORMS": os.environ.get("JAX_PLATFORMS")}
    os.environ["JAX_PLATFORMS"] = "cpu"
    ctx = multiprocessing.get_context("spawn")
    q = ctx.Queue()
    # each trainer owns one file (file-list sharding, data_set.cc semantics)
    procs = [ctx.Process(target=_trainer_proc,
                         args=(i, server.endpoint, [files[i]], epochs, q))
             for i in range(2)]
    try:
        for p in procs:
            p.start()
        results = {}
        for _ in range(2):
            tid, losses = q.get(timeout=300)
            results[tid] = losses
        for p in procs:
            p.join(timeout=30)
        for tid, losses in results.items():
            assert losses[-1] < losses[0] * 0.9, (tid, losses)
        # the sparse table actually holds learned rows
        keys, rows = server.params["ctr_emb"].table.dump()
        assert len(keys) > 0 and np.abs(rows).max() > 0
        # distributed curve lands in the local run's neighborhood
        local_final = np.mean(local_losses[-4:])
        dist_final = np.mean([np.mean(l[-4:]) for l in results.values()])
        assert abs(dist_final - local_final) < 0.25 * max(local_final, 0.3), \
            (dist_final, local_final)
    finally:
        for k, v in old_env.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        for p in procs:
            if p.is_alive():
                p.terminate()
        server.stop()
        PSClient.reset_all()
