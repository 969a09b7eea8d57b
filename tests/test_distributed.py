"""Multi-device behaviour (subprocess with 8 host-platform devices):
sharded serving parity, DP trainer with/without gradient compression,
elastic checkpoint-restart. Kept in subprocesses so the main test process
retains the real 1-device view."""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str, devices: int = 8, timeout: int = 600) -> str:
    env = dict(
        os.environ,
        XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
        PYTHONPATH=os.path.join(REPO, "src"),
    )
    out = subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True,
        text=True, timeout=timeout,
    )
    assert out.returncode == 0, f"stderr:\n{out.stderr[-3000:]}"
    return out.stdout


@pytest.mark.parametrize("shards", [0, 2, 3])
def test_host_mesh_rejects_shards_that_do_not_divide(shards):
    """A shard count the devices cannot be split into is a clear error
    (this process sees one device)."""
    import jax

    from repro.launch.mesh import make_host_mesh

    assert len(jax.devices()) == 1
    with pytest.raises(ValueError, match="cannot be split"):
        make_host_mesh(model_parallel=shards)


@pytest.mark.parametrize("cap", [8, 64])
def test_build_sharded_index_fixes_edge_capacity(cap):
    """``edge_capacity`` gives every shard the same labeled degree: wider
    rows keep their earliest tuples, narrower ones are padded; the device
    stack carries the matching label rows."""
    import numpy as np

    from repro.data import make_dataset
    from repro.launch.mesh import make_host_mesh
    from repro.serve import build_sharded_index

    vecs, s, t = make_dataset(256, 8, seed=3)
    free = build_sharded_index(vecs, s, t, "overlap", 2, M=8, Z=32)
    idx = build_sharded_index(vecs, s, t, "overlap", 2, M=8, Z=32,
                              edge_capacity=cap)
    assert idx.nbr.shape == (2, 128, cap)
    assert idx.labels.shape[:3] == (2, 128, cap)
    w = min(cap, free.nbr.shape[-1])
    np.testing.assert_array_equal(idx.nbr[..., :w], free.nbr[..., :w])
    np.testing.assert_array_equal(idx.labels[..., :w, :],
                                  free.labels[..., :w, :])
    assert (idx.nbr[..., w:] == -1).all()
    dev = idx.device(make_host_mesh(1))
    assert dev["labels"].shape == (2, 128, 1, -(-2 * cap // 128) * 128)


@pytest.mark.slow
def test_sharded_serving_recall_and_id_mapping():
    out = _run(
        """
import numpy as np
from repro.data import make_dataset, make_queries_vectors, generate_queries, ground_truth, recall_at_k
from repro.serve import build_sharded_index, serve_batch
from repro.launch.mesh import make_host_mesh
from repro.core import get_relation

vecs, s, t = make_dataset(1024, 12, seed=0)
qv = make_queries_vectors(16, 12, seed=1)
idx = build_sharded_index(vecs, s, t, "overlap", 4, M=8, Z=32)
mesh = make_host_mesh(model_parallel=4)
qs = ground_truth(generate_queries(qv, s, t, "overlap", 0.05, k=10, seed=2), vecs, s, t)
rel = get_relation("overlap")
for merge in ("all_gather", "tournament"):
    ids, d = serve_batch(idx, mesh, qs.vectors, qs.s_q, qs.t_q, k=10, beam=48, merge=merge)
    for i in range(qs.nq):
        m = rel.valid_mask(s, t, qs.s_q[i], qs.t_q[i])
        assert all(m[j] for j in ids[i] if j >= 0), (merge, i)
    r = recall_at_k(ids, qs)
    assert r >= 0.9, (merge, r)
    print(merge, round(r, 3))
""")
    assert "all_gather" in out and "tournament" in out


@pytest.mark.slow
def test_dp_trainer_and_gradient_compression():
    out = _run(
        """
import jax, numpy as np
from repro.configs import get_config
from repro.launch.mesh import make_host_mesh
from repro.models import init_params
from repro.train import adamw
from repro.train.dp_trainer import make_dp_train_step

cfg = get_config("llama3.2-1b", smoke=True)
mesh = make_host_mesh(model_parallel=1)   # 8-way DP
rng = np.random.default_rng(0)
batch = {"tokens": rng.integers(0, cfg.vocab_size, (8, 16)).astype(np.int32)}
batch["labels"] = np.roll(batch["tokens"], -1, axis=1)

losses = {}
for compress in (False, True):
    # fresh params per run: the jitted step donates its state argument
    params = init_params(cfg, jax.random.PRNGKey(0))
    opt = adamw(lr=1e-3)
    init_state, step = make_dp_train_step(cfg, opt, mesh, compress_grads=compress)
    state = init_state(params)
    ls = []
    for i in range(8):
        state, m = step(state, batch)
        ls.append(float(m["loss"]))
    losses[compress] = ls
    assert ls[-1] < ls[0], (compress, ls)
# int8-compressed training must track the uncompressed trajectory closely
diff = abs(losses[True][-1] - losses[False][-1])
assert diff < 0.15 * abs(losses[False][0] - losses[False][-1]) + 0.05, losses
print("ok", losses[False][-1], losses[True][-1])
""")
    assert "ok" in out


@pytest.mark.slow
def test_elastic_restart_downscale():
    out = _run(
        """
import jax, jax.numpy as jnp, numpy as np, tempfile
from jax.sharding import PartitionSpec as P
from repro.distributed.elastic import ElasticRunner
from repro.train import CheckpointManager, adamw

# toy quadratic model trained data-parallel; elastic 8 -> 4 devices
opt = adamw(lr=0.1, weight_decay=0.0)

def make_mesh(n):
    return jax.make_mesh((n,), ("data",))

def make_step(mesh):
    def step(state, batch):
        def loss_fn(p):
            pred = batch["x"] @ p["w"]
            return jnp.mean((pred - batch["y"]) ** 2)
        g = jax.grad(loss_fn)(state["params"])
        new_p, new_o, _ = opt.update(g, state["opt"], state["params"])
        return {"params": new_p, "opt": new_o}
    return jax.jit(step)

def state_specs(mesh):
    return jax.tree_util.tree_map(lambda _: P(),
        {"params": {"w": 0}, "opt": opt.init({"w": jnp.zeros((4,))})})

rng = np.random.default_rng(0)
w0 = {"w": jnp.asarray(rng.normal(size=(4,)).astype(np.float32))}
state = {"params": w0, "opt": opt.init(w0)}
batches = [{"x": rng.normal(size=(8, 4)).astype(np.float32),
            "y": rng.normal(size=(8,)).astype(np.float32)} for _ in range(30)]
with tempfile.TemporaryDirectory() as d:
    mgr = CheckpointManager(d, keep=2)
    runner = ElasticRunner(ckpt=mgr, make_mesh=make_mesh, make_step=make_step,
                           state_specs=state_specs, ckpt_every=5)
    state, steps, restarts = runner.run(state, batches, n_devices=8,
                                        fail_at=17, recover_devices=4)
assert steps == 30 and restarts == 1
print("elastic ok", steps, restarts)
""")
    assert "elastic ok" in out


@pytest.mark.slow
def test_dryrun_single_cell_subprocess():
    """The dry-run machinery itself (512 devices) on the cheapest cell."""
    out = _run(
        """
import sys
sys.argv = ["dryrun", "--arch", "llama3.2-1b", "--shape", "decode_32k",
            "--mesh", "single", "--out", "/tmp/dryrun_test"]
from repro.launch.dryrun import main
main()
import json
r = json.load(open("/tmp/dryrun_test/llama3.2-1b.decode_32k.pod16x16.json"))
assert r["ok"], r.get("error")
assert r["roofline"]["bottleneck"] in ("compute", "memory", "collective")
print("dryrun ok", r["roofline"]["bottleneck"])
""", devices=1, timeout=900)
    assert "dryrun ok" in out
