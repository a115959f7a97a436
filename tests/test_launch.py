"""Launcher / store / flight-recorder tests.

Reference test model: the new-style distributed tests shell out to the real
launcher (test/collective/test_communication_api_base.py:64 —
`python -m paddle.distributed.launch --devices …`), so the production
rendezvous path is exercised. Same here, on CPU.
"""
import json
import os
import subprocess
import sys
import textwrap
import threading
import time

import numpy as np
import pytest

pytestmark = pytest.mark.slow

import paddle_tpu as paddle
from paddle_tpu.distributed.store import TCPStore, TCPStoreServer
from paddle_tpu.distributed.flight_recorder import (
    enable_flight_recorder, disable_flight_recorder, get_flight_recorder)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- TCPStore ---------------------------------------------------------------
def test_store_set_get_add_delete():
    srv = TCPStoreServer()
    c = TCPStore("127.0.0.1", srv.port)
    c.set("k", "v1")
    assert c.get("k") == b"v1"
    assert c.get("missing") is None
    assert c.add("ctr", 3) == 3
    assert c.add("ctr", 2) == 5
    c.delete("k")
    assert c.get("k") is None
    assert sorted(c.list_keys("")) == ["ctr"]
    c.close()
    srv.close()


def test_store_wait_and_barrier_two_clients():
    srv = TCPStoreServer()

    def worker():
        c = TCPStore("127.0.0.1", srv.port)
        c.wait("go", timeout=10.0)
        c.barrier("b0", 2, timeout=10.0)
        c.set("done", "1")
        c.close()

    t = threading.Thread(target=worker)
    t.start()
    main = TCPStore("127.0.0.1", srv.port)
    time.sleep(0.2)
    main.set("go", "1")
    main.barrier("b0", 2, timeout=10.0)
    main.wait("done", timeout=10.0)
    t.join(timeout=10)
    assert not t.is_alive()
    with pytest.raises(TimeoutError):
        main.wait("never", timeout=0.3)
    main.close()
    srv.close()


# -- launcher end-to-end ----------------------------------------------------
WORKER_OK = textwrap.dedent("""
    import json, os, sys
    out = os.environ["TEST_OUT_DIR"]
    rank = os.environ["PADDLE_TRAINER_ID"]
    info = {k: os.environ.get(k) for k in
            ("PADDLE_TRAINER_ID", "PADDLE_TRAINERS_NUM", "PADDLE_LOCAL_RANK",
             "PADDLE_MASTER", "PADDLE_JOB_ID")}
    with open(os.path.join(out, f"rank{rank}.json"), "w") as f:
        json.dump(info, f)
""")

WORKER_ELASTIC = textwrap.dedent("""
    import os, sys
    # fail on the first job incarnation, succeed after elastic restart
    if os.environ["PADDLE_JOB_ID"] == "0":
        sys.exit(3)
    open(os.path.join(os.environ["TEST_OUT_DIR"],
         "ok" + os.environ["PADDLE_TRAINER_ID"]), "w").write("1")
""")


def _run_launch(tmp_path, worker_src, extra_args, env_extra=None):
    script = tmp_path / "worker.py"
    script.write_text(worker_src)
    env = dict(os.environ, TEST_OUT_DIR=str(tmp_path),
               PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH", ""))
    if env_extra:
        env.update(env_extra)
    return subprocess.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--log_dir", str(tmp_path / "log")] + extra_args + [str(script)],
        env=env, capture_output=True, text=True, timeout=120)


def test_launch_spawns_ranks_with_env(tmp_path):
    res = _run_launch(tmp_path, WORKER_OK, ["--nproc_per_node", "2"])
    assert res.returncode == 0, res.stderr
    infos = {}
    for r in (0, 1):
        with open(tmp_path / f"rank{r}.json") as f:
            infos[r] = json.load(f)
    assert infos[0]["PADDLE_TRAINERS_NUM"] == "2"
    assert infos[1]["PADDLE_TRAINER_ID"] == "1"
    assert infos[0]["PADDLE_MASTER"].startswith("127.0.0.1:")


def test_launch_elastic_restart(tmp_path):
    res = _run_launch(tmp_path, WORKER_ELASTIC,
                      ["--nproc_per_node", "2", "--elastic_retries", "2"])
    assert res.returncode == 0, res.stderr
    assert (tmp_path / "ok0").exists() and (tmp_path / "ok1").exists()
    assert "elastic restart" in res.stderr


def test_launch_failure_propagates(tmp_path):
    res = _run_launch(tmp_path, "import sys; sys.exit(7)", [])
    assert res.returncode == 7


# -- flight recorder --------------------------------------------------------
def test_flight_recorder_records_and_dumps(tmp_path):
    import paddle_tpu.distributed as dist
    dump = tmp_path / "fr.json"
    rec = enable_flight_recorder(timeout=3600.0, dump_path=str(dump))
    try:
        t = paddle.to_tensor(np.ones((4,), np.float32))
        dist.all_reduce(t)
        dist.broadcast(t, src=0)
        tasks = rec.tasks()
        assert len(tasks) == 2
        assert tasks[0].op == "all_reduce"
        assert tasks[0].shape == (4,)
        assert not tasks[0].pending
        rec.dump(reason="test")
        report = json.loads(dump.read_text())
        assert report["reason"] == "test"
        assert len(report["entries"]) == 2
        # reduce is built on all_reduce: must record ONE logical entry
        dist.reduce(t, dst=0)
        assert [x.op for x in rec.tasks()].count("reduce") == 1
        assert "all_reduce" not in [x.op for x in rec.tasks()[2:]]
        # group passed positionally still records the axis
        from paddle_tpu.distributed.topology import CommGroup
        dist.all_reduce(t, dist.ReduceOp.SUM, CommGroup("mp", [0], 0))
        assert rec.tasks()[-1].axis == "mp"
        # alltoall alias is instrumented; payload tensor shape is captured
        o1 = paddle.to_tensor(np.zeros((2,), np.float32))
        o2 = paddle.to_tensor(np.zeros((2,), np.float32))
        i1 = paddle.to_tensor(np.ones((2,), np.float32))
        i2 = paddle.to_tensor(np.ones((2,), np.float32))
        dist.alltoall([o1, o2], [i1, i2])
        assert rec.tasks()[-1].op == "all_to_all"
        out_lists = [paddle.to_tensor(np.zeros((3,), np.float32))]
        dist.all_gather(out_lists, paddle.to_tensor(
            np.ones((3,), np.float32)))
        assert rec.tasks()[-1].shape == (3,)
    finally:
        disable_flight_recorder()


def test_flight_recorder_disabled_no_overhead():
    import paddle_tpu.distributed as dist
    rec = get_flight_recorder()
    assert not rec.enabled
    t = paddle.to_tensor(np.ones((2,), np.float32))
    dist.all_reduce(t)   # should not record
    assert all(x.op != "all_reduce" or x.end_ts for x in rec.tasks())


class TestElasticManager:
    """Membership + re-rank over the store (reference:
    fleet/elastic/manager.py:126; test pattern:
    test_fleet_elastic_manager.py with a mocked registry)."""

    def _store(self):
        from paddle_tpu.distributed.store import TCPStoreServer, TCPStore
        srv = TCPStoreServer(port=0)
        return srv, TCPStore("127.0.0.1", srv.port)

    def test_membership_and_rerank(self):
        from paddle_tpu.distributed.launch.elastic import ElasticManager
        srv, store = self._store()
        try:
            a = ElasticManager(store, node_id="hostB", min_nodes=1)
            b = ElasticManager(store, node_id="hostA", min_nodes=1)
            a.register()
            b.register()
            # rank order is sorted node id: hostA=0, hostB=1
            n, r = a.resolve(timeout=10, settle=0.3)
            assert (n, r) == (2, 1)
            n, r = b.resolve(timeout=10, settle=0.3)
            assert (n, r) == (2, 0)
        finally:
            srv.close()

    def test_scale_in_detection_and_rerank(self):
        import time as _t
        from paddle_tpu.distributed.launch.elastic import ElasticManager
        srv, store = self._store()
        try:
            a = ElasticManager(store, node_id="n0", min_nodes=1,
                               heartbeat_ttl=0.6)
            b = ElasticManager(store, node_id="n1", min_nodes=1,
                               heartbeat_ttl=0.6)
            a.register()
            b.register()
            assert a.resolve(timeout=10, settle=0.3) == (2, 0)
            # n1 leaves (stops heartbeating)
            b.leave()
            _t.sleep(0.1)
            assert a.scale_event() == "scale_in"
            n, r = a.resolve(timeout=10, settle=0.3)
            assert (n, r) == (1, 0)
            # n1 rejoins -> scale_out
            b.heartbeat()
            assert a.scale_event() == "scale_out"
            assert a.resolve(timeout=10, settle=0.3) == (2, 0)
        finally:
            srv.close()

    def test_bounds_block_resolution(self):
        from paddle_tpu.distributed.launch.elastic import ElasticManager
        srv, store = self._store()
        try:
            a = ElasticManager(store, node_id="solo", min_nodes=2)
            a.register()
            import pytest as _pytest
            with _pytest.raises(TimeoutError):
                a.resolve(timeout=1.5)
        finally:
            srv.close()


# -- elastic end-to-end -----------------------------------------------------
def test_elastic_end_to_end(tmp_path):
    """VERDICT r4 Next #6 — the full failover loop through the REAL
    stack: 4 single-trainer nodes train a GSPMD-sharded model over gloo;
    the node-3 trainer dies hard mid-run; the surviving controllers
    detect the stale heartbeat, re-rank via the ElasticManager to a
    3-node world, respawn, and the workers resume from the 4-way-sharded
    distributed checkpoint loaded onto the 3-device mesh
    (reshard-on-load). The resumed trajectory must exactly continue the
    pre-crash one. Reference: fleet/elastic/manager.py:126 (watch ->
    re-rank -> relaunch) + checkpoint/load_state_dict.py:526."""
    import socket as _socket
    s = _socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()

    worker = os.path.join(REPO, "tests", "elastic_worker.py")
    out_dir = tmp_path / "out"
    out_dir.mkdir()
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "PYTHONPATH": REPO + os.pathsep + env.get("PYTHONPATH", ""),
        "PADDLE_ELASTIC_MIN": "2", "PADDLE_ELASTIC_MAX": "4",
        "PADDLE_HEARTBEAT_INTERVAL": "0.5",
        "PADDLE_HEARTBEAT_STALE": "3",
        "PADDLE_ELASTIC_TTL": "5", "PADDLE_ELASTIC_SETTLE": "2",
        "ELASTIC_VICTIM": "3",
    })
    procs = []
    for node in range(4):
        procs.append(subprocess.Popen(
            [sys.executable, "-m", "paddle_tpu.distributed.launch",
             "--nnodes", "4", "--node_rank", str(node),
             "--nproc_per_node", "1",
             "--master", f"127.0.0.1:{port}",
             "--elastic_retries", "0" if node == 3 else "2",
             "--log_dir", str(tmp_path / f"log{node}"),
             worker, str(out_dir)],
            env=env, cwd=REPO, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True))
    outs = {}
    try:
        # generous bound: ~52s standalone, but xdist runs this next to
        # other multi-process tests on a shared box
        for node, p in enumerate(procs):
            outs[node] = p.communicate(timeout=420)[0]
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()

    logs = "\n\n".join(f"== node {n} ==\n{o[-3000:]}"
                       for n, o in outs.items())
    # victim node fails; survivors finish clean after the re-ranked run
    assert procs[3].returncode != 0, logs
    for node in range(3):
        assert procs[node].returncode == 0, logs

    results = {}
    for r in range(3):
        f = out_dir / f"rank{r}_job1.json"
        assert f.exists(), f"rank {r} job 1 wrote no result\n{logs}"
        results[r] = json.loads(f.read_text())
    for r, res in results.items():
        assert res["world"] == 3, logs
        assert res["start"] == 5, (res, logs)  # resumed, not restarted

    # the resumed trajectory must exactly continue deterministic GD
    import importlib.util
    spec = importlib.util.spec_from_file_location("elastic_worker", worker)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    COLS, CRASH_STEP, LR, N, ROWS, TOTAL_STEPS = (
        mod.COLS, mod.CRASH_STEP, mod.LR, mod.N, mod.ROWS,
        mod.TOTAL_STEPS)
    rng = np.random.RandomState(0)
    A = rng.randn(N, ROWS).astype(np.float32)
    b = rng.randn(N, COLS).astype(np.float32)
    w = rng.randn(ROWS, COLS).astype(np.float32) * 0.1
    losses = []
    for _ in range(TOTAL_STEPS):
        r_ = A @ w - b
        losses.append(float((r_ ** 2).mean()))
        w = w - LR * (2.0 / N / COLS) * (A.T @ r_)
    np.testing.assert_allclose(results[0]["losses"],
                               losses[CRASH_STEP:], rtol=1e-3,
                               err_msg=logs[-1500:])
    assert results[0]["losses"][-1] < losses[CRASH_STEP - 1], \
        "loss did not keep descending after failover"
    # reassemble the 3-way-sharded final weights from per-rank shards
    w_got = np.zeros_like(w)
    for res in results.values():
        off = res["w_offset"]
        loc = np.asarray(res["w_local"], np.float32)
        w_got[off:off + loc.shape[0]] = loc
    np.testing.assert_allclose(w_got, w, rtol=1e-3, atol=1e-5)
