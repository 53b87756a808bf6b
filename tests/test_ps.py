"""Parameter-server stack tests: native table math, server/client transport,
sync aggregation, and transpiled end-to-end training (loss parity with the
single-process run — the reference's TestDistBase assertion,
unittests/test_dist_base.py:506)."""
import multiprocessing
import threading

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.distributed import (DenseTable, ParameterServer, PSClient,
                                    SparseTable)
from paddle_tpu.transpiler.distribute_transpiler import (
    DistributeTranspiler, DistributeTranspilerConfig)


# ---------------------------------------------------------------------------
# native table math
# ---------------------------------------------------------------------------

def test_dense_table_sgd_adagrad_adam():
    rng = np.random.RandomState(0)
    w0 = rng.randn(4, 3).astype(np.float32)
    g = rng.randn(4, 3).astype(np.float32)

    t = DenseTable((4, 3), "sgd", lr=0.1)
    t.set(w0)
    t.push(g)
    np.testing.assert_allclose(t.pull(), w0 - 0.1 * g, rtol=1e-6)

    t = DenseTable((4, 3), "adagrad", lr=0.1)
    t.set(w0)
    t.push(g)
    want = w0 - 0.1 * g / (np.sqrt(g * g) + 1e-6)
    np.testing.assert_allclose(t.pull(), want, rtol=1e-5)

    t = DenseTable((4, 3), "adam", lr=0.1)
    t.set(w0)
    t.push(g)
    m = 0.1 * g
    v = 0.001 * g * g
    mhat = m / (1 - 0.9)
    vhat = v / (1 - 0.999)
    want = w0 - 0.1 * mhat / (np.sqrt(vhat) + 1e-8)
    np.testing.assert_allclose(t.pull(), want, rtol=1e-4)

    t = DenseTable((4, 3), "momentum", lr=0.1)
    t.set(w0)
    t.push(g)
    t.push(g)
    # v1 = g; w1 = w0 - .1 g; v2 = .9 g + g; w2 = w1 - .1 v2
    want = w0 - 0.1 * g - 0.1 * (0.9 * g + g)
    np.testing.assert_allclose(t.pull(), want, rtol=1e-5)


def test_sparse_table():
    t = SparseTable(4, "sgd", lr=1.0)
    keys = np.array([7, 42], np.uint64)
    # unseen rows pull zeros
    np.testing.assert_allclose(t.pull(keys), 0.0)
    g = np.ones((2, 4), np.float32)
    t.push(keys, g)
    np.testing.assert_allclose(t.pull(keys), -1.0)
    assert len(t) == 2
    t.set(np.array([7], np.uint64), np.full((1, 4), 5.0, np.float32))
    np.testing.assert_allclose(t.pull(np.array([7], np.uint64)), 5.0)
    dk, dv = t.dump()
    assert set(dk.tolist()) == {7, 42}


# ---------------------------------------------------------------------------
# server/client transport
# ---------------------------------------------------------------------------

def test_server_pull_push_roundtrip():
    server = ParameterServer("127.0.0.1:0", trainer_num=1, sync_mode=False)
    server.register_dense("w", (3,), "sgd", lr=0.5)
    server.start()
    try:
        client = PSClient(trainer_id=0)
        client.ensure_init(server.endpoint, "w", np.array([1., 2., 3.], np.float32))
        np.testing.assert_allclose(client.pull(server.endpoint, "w"), [1, 2, 3])
        client.push(server.endpoint, "w", np.ones(3, np.float32), lr=0.5)
        np.testing.assert_allclose(client.pull(server.endpoint, "w"),
                                   [0.5, 1.5, 2.5])
        # sparse
        server.register_sparse("emb", 2, "sgd", lr=1.0)
        client.push_sparse(server.endpoint, "emb",
                           np.array([3], np.uint64), -np.ones((1, 2), np.float32))
        np.testing.assert_allclose(
            client.pull_sparse(server.endpoint, "emb",
                               np.array([3], np.uint64)), 1.0)
        client.close()
    finally:
        server.stop()


def test_sync_push_aggregates_two_trainers():
    server = ParameterServer("127.0.0.1:0", trainer_num=2, sync_mode=True)
    server.register_dense("w", (2,), "sgd", lr=1.0)
    server.start()
    try:
        c0 = PSClient(trainer_id=0)
        c0.ensure_init(server.endpoint, "w", np.zeros(2, np.float32))

        def trainer1():
            c1 = PSClient(trainer_id=1)
            c1.push(server.endpoint, "w", np.array([3., 3.], np.float32), lr=1.0)
            c1.close()

        t = threading.Thread(target=trainer1)
        t.start()
        c0.push(server.endpoint, "w", np.array([1., 1.], np.float32), lr=1.0)
        t.join(timeout=10)
        assert not t.is_alive()
        # applied once with the averaged grad: w = 0 - (1+3)/2 = -2
        np.testing.assert_allclose(c0.pull(server.endpoint, "w"), [-2., -2.])
        c0.close()
    finally:
        server.stop()


# ---------------------------------------------------------------------------
# transpiled end-to-end: 1 trainer, in-process pserver
# ---------------------------------------------------------------------------

def _build_regression(seed=0):
    from paddle_tpu.framework import unique_name
    prog, startup = fluid.Program(), fluid.Program()
    prog.random_seed = startup.random_seed = seed
    with unique_name.guard():
        with fluid.program_guard(prog, startup):
            x = fluid.layers.data("x", [4], dtype="float32")
            y = fluid.layers.data("y", [1], dtype="float32")
            pred = fluid.layers.fc(x, 1)
            loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
            fluid.optimizer.SGDOptimizer(0.1).minimize(loss)
    return prog, startup, loss


def _regression_data(n=64, seed=3):
    rng = np.random.RandomState(seed)
    w = np.array([1., -2., 3., 0.5], np.float32)
    x = rng.randn(n, 4).astype(np.float32)
    y = (x @ w).reshape(-1, 1).astype(np.float32)
    return x, y


def test_transpiled_training_matches_local():
    x, y = _regression_data()

    # local baseline
    prog, startup, loss = _build_regression()
    exe = fluid.Executor(fluid.XLAPlace(0))
    local_scope = fluid.Scope()
    exe.run(startup, scope=local_scope)
    local_losses = [float(exe.run(prog, feed={"x": x, "y": y},
                                  fetch_list=[loss], scope=local_scope)[0])
                    for _ in range(10)]

    # PS run: same program transpiled, server in-process; fresh Executor so
    # the startup rng stream matches the baseline's (rng folds in exe step)
    PSClient.reset_all()
    exe = fluid.Executor(fluid.XLAPlace(0))
    prog2, startup2, loss2 = _build_regression()
    t = DistributeTranspiler()
    t.transpile(trainer_id=0, program=prog2, pservers="127.0.0.1:0",
                trainers=1, sync_mode=True)
    # bind the server first to learn its real port
    pserver_prog = t.get_pserver_program("127.0.0.1:0")
    ls_op = pserver_prog.global_block().ops[0]
    ls_op.attrs["blocking"] = False
    exe.run(pserver_prog)  # starts the server thread
    server = ls_op._server
    try:
        # rewrite trainer endpoints to the bound port
        trainer_prog = t.get_trainer_program()
        for op in trainer_prog.global_block().ops:
            if "epmap" in op.attrs:
                op.attrs["epmap"] = [server.endpoint]
            if "endpoints" in op.attrs:
                op.attrs["endpoints"] = [server.endpoint]
        ps_scope = fluid.Scope()
        exe.run(startup2, scope=ps_scope)
        # identical init: copy local baseline's initial params
        ps_losses = [float(exe.run(trainer_prog, feed={"x": x, "y": y},
                                   fetch_list=[loss2], scope=ps_scope)[0])
                     for _ in range(10)]
    finally:
        PSClient.instance(0).stop_server(server.endpoint)
        PSClient.reset_all()

    # both runs start from their own random init (same seed => same init),
    # and sgd-on-server matches sgd-locally => loss curves match closely
    np.testing.assert_allclose(ps_losses, local_losses, rtol=2e-3, atol=2e-4)
    assert ps_losses[-1] < ps_losses[0] * 0.2


def _trainer_proc(trainer_id, endpoint, x, y, steps, q):
    """Spawned trainer process (reference test_dist_base.py _run_cluster
    pattern: real processes on one host)."""
    import os
    assert os.environ.get("JAX_PLATFORMS") == "cpu"  # set by the parent:
    # spawned children stay off any accelerator (env is read at jax import,
    # which happens during child bootstrap — before this function runs)
    import numpy as np
    import paddle_tpu as fluid
    from paddle_tpu.transpiler.distribute_transpiler import DistributeTranspiler

    prog, startup, loss = _build_regression()
    t = DistributeTranspiler()
    t.transpile(trainer_id=trainer_id, program=prog, pservers=endpoint,
                trainers=2, sync_mode=True)
    trainer_prog = t.get_trainer_program()
    exe = fluid.Executor(fluid.XLAPlace(0))
    scope = fluid.Scope()
    exe.run(startup, scope=scope)
    losses = []
    for _ in range(steps):
        out = exe.run(trainer_prog, feed={"x": x, "y": y},
                      fetch_list=[loss], scope=scope)
        losses.append(float(out[0]))
    from paddle_tpu.distributed import PSClient
    w_final = PSClient.instance(trainer_id).pull(endpoint, "fc_0.w_0")
    PSClient.instance(trainer_id).complete([endpoint])
    q.put((trainer_id, losses, np.asarray(w_final)))


def test_two_trainer_cluster_matches_local():
    """2 real trainer processes + sync pserver == local full-batch SGD."""
    x, y = _regression_data(n=64)
    steps = 6

    # local full-batch baseline
    prog, startup, loss = _build_regression()
    exe = fluid.Executor(fluid.XLAPlace(0))
    scope = fluid.Scope()
    exe.run(startup, scope=scope)
    for _ in range(steps):
        exe.run(prog, feed={"x": x, "y": y}, fetch_list=[loss], scope=scope)
    w_local = np.asarray(scope.find_var("fc_0.w_0"))

    server = ParameterServer("127.0.0.1:0", trainer_num=2, sync_mode=True)
    server.register_dense("fc_0.w_0", (4, 1), "sgd")
    server.register_dense("fc_0.b_0", (1,), "sgd")
    server.start()
    import os
    old_env = {"JAX_PLATFORMS": os.environ.get("JAX_PLATFORMS")}
    # children must be pure-CPU: a chip belongs to one process at a time
    os.environ["JAX_PLATFORMS"] = "cpu"
    ctx = multiprocessing.get_context("spawn")
    q = ctx.Queue()
    procs = [ctx.Process(target=_trainer_proc,
                         args=(i, server.endpoint, x[i::2], y[i::2], steps, q))
             for i in range(2)]
    try:
        for p in procs:
            p.start()
        results = {}
        for _ in range(2):
            tid, losses, w = q.get(timeout=180)
            results[tid] = (losses, w)
        for p in procs:
            p.join(timeout=30)
        # both trainers converge and see identical server params
        for tid, (losses, w) in results.items():
            assert losses[-1] < losses[0], (tid, losses)
        np.testing.assert_allclose(results[0][1], results[1][1], rtol=1e-6)
        # sync avg of the two half-batch grads == full-batch grad
        np.testing.assert_allclose(results[0][1], w_local, rtol=2e-3,
                                   atol=2e-4)
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


def test_distributed_lookup_table_op():
    """Remote sparse embedding lookup inside a program (parameter_prefetch
    capability): ids -> pserver sparse table rows -> downstream device ops."""
    server = ParameterServer("127.0.0.1:0", trainer_num=1, sync_mode=False)
    server.register_sparse("emb_table", 3, "sgd", lr=1.0)
    server.start()
    try:
        c = PSClient.instance(0)
        keys = np.array([5, 9], np.uint64)
        c.push_sparse(server.endpoint, "emb_table", keys,
                      -np.arange(6, dtype=np.float32).reshape(2, 3))

        prog = fluid.Program()
        block = prog.global_block()
        ids = block.create_var(name="ids", shape=[-1, 1], dtype="int64",
                               is_data=True)
        emb = block.create_var(name="emb_out", shape=[-1, 3], dtype="float32")
        out = block.create_var(name="doubled", shape=[-1, 3], dtype="float32")
        block.append_op(
            type="distributed_lookup_table",
            inputs={"Ids": ["ids"]}, outputs={"Out": ["emb_out"]},
            attrs={"epmap": [server.endpoint], "table_name": "emb_table",
                   "trainer_id": 0})
        block.append_op(type="scale", inputs={"X": ["emb_out"]},
                        outputs={"Out": ["doubled"]},
                        attrs={"scale": 2.0, "bias": 0.0})

        exe = fluid.Executor(fluid.XLAPlace(0))
        scope = fluid.Scope()
        import jax.numpy as jnp
        scope.set_var("ids", jnp.asarray(np.array([[5], [9]], np.int64)))
        vals = exe.run(prog, feed={}, fetch_list=["doubled"], scope=scope)
        np.testing.assert_allclose(
            vals[0], 2.0 * np.arange(6, dtype=np.float32).reshape(2, 3))
        PSClient.reset_all()
    finally:
        server.stop()


def test_checkpoint_notify(tmp_path):
    server = ParameterServer("127.0.0.1:0", trainer_num=1, sync_mode=False)
    server.register_dense("w", (2,), "sgd", lr=1.0)
    server.start()
    try:
        c = PSClient(trainer_id=0)
        c.ensure_init(server.endpoint, "w", np.array([4., 5.], np.float32))
        c.checkpoint_notify(server.endpoint, str(tmp_path / "ck"))
        saved = np.load(str(tmp_path / "ck" / "w.npy"))
        np.testing.assert_allclose(saved, [4., 5.])
        c.close()
    finally:
        server.stop()


def test_transpiler_forwards_optimizer_hparams():
    """Momentum's mu / adam's betas must reach the pserver table config
    (advisor round-1 finding: server silently used hardcoded defaults)."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", [4], dtype="float32")
        y = fluid.layers.data("y", [1], dtype="float32")
        pred = fluid.layers.fc(x, 1)
        loss = fluid.layers.reduce_mean(fluid.layers.square(pred - y))
        fluid.optimizer.MomentumOptimizer(0.1, momentum=0.5).minimize(loss)
    t = DistributeTranspiler()
    t.transpile(trainer_id=0, program=main, pservers="127.0.0.1:16217",
                trainers=1)
    prog = t.get_pserver_program("127.0.0.1:16217")
    ls = [op for op in prog.global_block().ops
          if op.type == "listen_and_serv"][0]
    tables = ls.attr("tables")
    assert tables, "no tables in listen_and_serv"
    by_opt = {tbl["optimizer"]: tbl for tbl in tables}
    assert "momentum" in by_opt
    assert by_opt["momentum"]["hparams"]["beta1"] == 0.5
