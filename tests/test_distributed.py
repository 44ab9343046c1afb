"""Multi-device tests (8 fake CPU devices via a subprocess so the main pytest
process keeps the default single-device view, per the dry-run isolation rule).
"""
import os
import subprocess
import sys

import pytest

_SCRIPT = r"""
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import accuracy, bank_take, fit, fit_bank
from repro.launch.mesh import make_host_mesh
from repro.optim.grad_compress import (
    ef_init, compress_grads_topk, int8_quant, int8_dequant,
)
from repro.sharding import rules as R
from repro.train import TrainCfg, init_state, make_train_step
from repro.models import build_model
from repro.configs import get_config

assert len(jax.devices()) == 8, jax.devices()

# --- sharded one-pass SVM vs sequential -----------------------------------
rng = np.random.default_rng(0)
N, D = 4096, 32
X = rng.normal(size=(N, D)).astype(np.float32)
y = np.sign(rng.normal(size=N) + 2 * X[:, 0]).astype(np.float32); y[y == 0] = 1
X /= np.linalg.norm(X, axis=1, keepdims=True)  # K(x,x)=1 assumption
mesh = jax.make_mesh((8,), ("data",))
bs = bank_take(fit_bank(jnp.asarray(X), jnp.asarray(y)[None], 10.0, mesh=mesh), 0)
bq = fit(jnp.asarray(X), jnp.asarray(y), 10.0)
acc_s = float(accuracy(bs, jnp.asarray(X), jnp.asarray(y)))
acc_q = float(accuracy(bq, jnp.asarray(X), jnp.asarray(y)))
assert abs(acc_s - acc_q) < 0.08, (acc_s, acc_q)
# merged ball must still enclose in the radius sense (bounded degradation)
assert float(bs.r) <= 2.0 * float(bq.r), (float(bs.r), float(bq.r))

# --- sharded LM train step on a 4x2 mesh -----------------------------------
mesh2 = make_host_mesh(8, model_axis=2)
cfg = get_config("internlm2-1.8b", smoke=True)
model = build_model(cfg)
tcfg = TrainCfg(microbatches=2, peak_lr=1e-3, warmup_steps=1, total_steps=10)
state = init_state(model, jax.random.PRNGKey(0), tcfg)
step = make_train_step(model, tcfg)
p_sh = R.tree_shardings(state["params"], mesh2, R.param_spec)
batch = {
    "tokens": jnp.ones((8, 64), jnp.int32),
    "targets": jnp.ones((8, 64), jnp.int32),
}
b_sh = R.tree_shardings(batch, mesh2, R.batch_spec)
from repro.optim import adamw
state_sh = {"params": p_sh, "opt": adamw.AdamWState(m=p_sh, v=p_sh,
            step=jax.NamedSharding(mesh2, P()))}
with mesh2:
    jstep = jax.jit(step, in_shardings=(state_sh, b_sh), out_shardings=(state_sh, None))
    st, metrics = jstep(state, batch)
    l0 = float(metrics["loss"])
    for _ in range(4):
        st, metrics2 = jstep(st, batch)
assert np.isfinite(l0)
assert float(metrics2["loss"]) < l0  # same batch repeatedly -> loss drops

# --- gradient compression --------------------------------------------------
g = {"w": jnp.asarray(rng.normal(size=(64, 64)).astype(np.float32))}
ef = ef_init(g)
d1, ef = compress_grads_topk(g, ef, frac=0.1)
# error feedback: residual + dense == original
np.testing.assert_allclose(
    np.asarray(d1["w"] + ef.residual["w"]), np.asarray(g["w"]), rtol=1e-6)
q, s = int8_quant(g["w"])
err = np.abs(np.asarray(int8_dequant(q, s)) - np.asarray(g["w"])).max()
assert err <= float(s) * 0.51 + 1e-6

print("DISTRIBUTED_OK")
"""


@pytest.mark.slow
def test_distributed_subprocess():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src")
    )
    out = subprocess.run(
        [sys.executable, "-c", _SCRIPT], env=env, capture_output=True, text=True,
        timeout=900,
    )
    assert out.returncode == 0, f"stdout:{out.stdout[-2000:]}\nstderr:{out.stderr[-4000:]}"
    assert "DISTRIBUTED_OK" in out.stdout
