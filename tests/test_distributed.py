"""Multi-host runtime tests (parallel/distributed.py).

The single-process degenerate case runs in-process; the real
jax.distributed path launches two subprocesses over a localhost
coordinator (the reference's cluster-launch plane analog,
Runner.scala:92-210) and checks the 2-host sharded training matches the
single-process result bit-for-bit-ish.
"""

import json
import os
import socket
import subprocess
import sys

import numpy as np
import pytest

from predictionio_tpu.parallel import distributed

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class TestDegenerateSingleHost:
    def test_initialize_noop_on_one_host(self):
        cfg = distributed.DistributedConfig()
        assert not cfg.is_multi_host
        assert distributed.initialize(cfg) is False
        assert distributed.process_count() == 1
        assert distributed.process_index() == 0

    def test_multi_host_requires_coordinator_and_id(self):
        with pytest.raises(ValueError, match="coordinator"):
            distributed.initialize(
                distributed.DistributedConfig(num_hosts=2))
        with pytest.raises(ValueError, match="process-id"):
            distributed.initialize(distributed.DistributedConfig(
                num_hosts=2, coordinator="127.0.0.1:1"))

    def test_config_from_env(self, monkeypatch):
        monkeypatch.setenv("PIO_COORDINATOR", "h0:8476")
        monkeypatch.setenv("PIO_NUM_HOSTS", "4")
        monkeypatch.setenv("PIO_PROCESS_ID", "2")
        cfg = distributed.DistributedConfig.from_env()
        assert (cfg.coordinator, cfg.num_hosts, cfg.process_id) == \
            ("h0:8476", 4, 2)
        assert cfg.is_multi_host

    def test_host_aware_mesh_local(self):
        mesh = distributed.host_aware_mesh()
        assert mesh.axis_names == ("data",)
        mesh2 = distributed.host_aware_mesh(model=2)
        assert mesh2.axis_names == ("data", "model")
        assert mesh2.shape["model"] == 2

    def test_row_blocks_partition_everything(self):
        for n, k in [(10, 3), (8, 8), (7, 2), (5, 1), (0, 2)]:
            blocks = [distributed.process_row_block(n, i, k)
                      for i in range(k)]
            assert blocks[0][0] == 0 and blocks[-1][1] == n
            for (a, b), (c, d) in zip(blocks, blocks[1:]):
                assert b == c        # contiguous, no gap/overlap
            sizes = [b - a for a, b in blocks]
            assert max(sizes) - min(sizes) <= 1  # balanced

    def test_row_block_index_validation(self):
        with pytest.raises(ValueError):
            distributed.process_row_block(10, 3, 3)


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


@pytest.mark.multichip
def test_two_process_training_matches_single(tmp_path):
    """Launch 2 real host processes (2 virtual CPU devices each) through
    jax.distributed; the 4-device global-mesh training must match the
    in-process single-host result."""
    port = _free_port()
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    env["PYTHONPATH"] = REPO

    procs = [
        subprocess.Popen(
            [sys.executable, os.path.join(REPO, "tests",
                                          "multihost_worker.py"),
             f"127.0.0.1:{port}", "2", str(pid)],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, env=env,
            text=True)
        for pid in range(2)
    ]
    outs = []
    for p in procs:
        out, err = p.communicate(timeout=240)
        if p.returncode != 0 and \
                "aren't implemented on the CPU backend" in err:
            # env artifact (triaged PR 6): this jaxlib's CPU client has
            # no multi-process collectives — the workers initialize and
            # build the 2-host mesh, but the first sharded dispatch
            # raises INVALID_ARGUMENT. Real multi-host runs (TPU) are
            # unaffected; nothing to fix on our side.
            for q in procs:
                q.kill()
            pytest.skip("jaxlib CPU backend lacks multi-process "
                        "collectives (XlaRuntimeError: Multiprocess "
                        "computations aren't implemented on the CPU "
                        "backend)")
        assert p.returncode == 0, f"worker failed:\n{out}\n{err}"
        outs.append(json.loads(out.strip().splitlines()[-1]))

    assert all(o["devices"] == 4 for o in outs)
    # both hosts computed (and allgathered) identical factors
    assert outs[0]["x_sum"] == pytest.approx(outs[1]["x_sum"], rel=1e-6)

    # reference: the same problem single-process on one device
    from predictionio_tpu.ops.als import train_als_bucketed
    from tests.multihost_worker import make_problem

    user_side, item_side, params = make_problem()
    X, Y = train_als_bucketed(user_side, item_side, params)
    assert outs[0]["x_sum"] == pytest.approx(float(np.abs(X).sum()),
                                             rel=1e-4)
    np.testing.assert_allclose(np.asarray(outs[0]["x_row0"]), X[0],
                               rtol=1e-4, atol=1e-5)


def test_secondary_host_skips_persistence(mem_storage, monkeypatch):
    """On a non-primary host run_train trains but writes neither an
    EngineInstance nor a Model blob (driver-persists semantics,
    CoreWorkflow.scala:74-86)."""
    from predictionio_tpu.controller import ComputeContext, EngineParams
    from predictionio_tpu.controller.engine import Engine
    from predictionio_tpu.data import storage
    from predictionio_tpu.workflow import run_train
    from predictionio_tpu.workflow.create_workflow import (
        WorkflowConfig, new_engine_instance,
    )
    from tests.dase_fixtures import (
        DataSource0, IdParams, P2LAlgo0, Preparator0, Serving0,
    )

    monkeypatch.setattr(distributed, "_INITIALIZED", True)
    monkeypatch.setattr(distributed, "process_index", lambda: 1)
    assert not distributed.is_primary_host()

    engine = Engine(DataSource0, Preparator0, {"": P2LAlgo0}, Serving0)
    params = EngineParams(
        data_source_params=("", IdParams(1)),
        preparator_params=("", IdParams(2)),
        algorithm_params_list=[("", IdParams(3))],
        serving_params=("", IdParams(9)),
    )
    cfg = WorkflowConfig(engine_id="e", engine_version="1",
                         engine_variant="v.json")
    iid = run_train(engine, params, new_engine_instance(cfg, params),
                    ctx=ComputeContext())
    assert iid is None
    assert storage.get_metadata_engine_instances().get_latest_completed(
        "e", "1", "v.json") is None
