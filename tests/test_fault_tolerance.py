"""Fault-tolerance depth: preemption signals and elastic re-mesh restore."""
import json
import os
import signal
import subprocess
import sys

import jax
import pytest

from repro.configs import smoke_config
from repro.data.tokens import SyntheticTokens
from repro.models import transformer as T
from repro.train import checkpoint as ckpt
from repro.train.optimizer import OptConfig
from repro.train.trainer import TrainConfig, Trainer


@pytest.mark.slow
def test_sigterm_checkpoints_and_stops(tmp_path):
    """The cloud preemption contract: SIGTERM ⇒ save state, exit the loop."""
    cfg = smoke_config("internlm2-1.8b")
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    data = SyntheticTokens(vocab_size=cfg.vocab_size, batch=4, seq_len=16)
    tcfg = TrainConfig(opt=OptConfig(lr=1e-3), checkpoint_every=1000,
                       checkpoint_dir=str(tmp_path), log_every=1000)
    trainer = Trainer(cfg, tcfg, params, iter(data))
    trainer.install_signal_handlers()
    trainer.run(2)                           # warm up two steps
    os.kill(os.getpid(), signal.SIGTERM)     # delivery is synchronous enough:
    trainer.run(50)                          # loop must stop early + save
    assert trainer.step < 52
    assert ckpt.latest_step(str(tmp_path)) == trainer.step

    # restart resumes exactly where the preemption checkpoint left off
    t2 = Trainer(cfg, tcfg, T.init_params(cfg, jax.random.PRNGKey(7)),
                 iter(data))
    assert t2.restore()
    assert t2.step == trainer.step


ELASTIC_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.train import checkpoint as ckpt

from repro.utils import make_auto_mesh
mesh_a = make_auto_mesh((2, 4), ("data", "model"))
mesh_b = make_auto_mesh((8,), ("data",))

tree = {"w": jnp.arange(64.0).reshape(8, 8), "b": jnp.arange(8.0)}
sharded = {
    "w": jax.device_put(tree["w"], NamedSharding(mesh_a, P("data", "model"))),
    "b": jax.device_put(tree["b"], NamedSharding(mesh_a, P("model"))),
}
path = ckpt.save("/tmp/elastic_ckpt", sharded, step=3)

# restore onto a DIFFERENT mesh topology (8-way pure data)
new_sh = {
    "w": NamedSharding(mesh_b, P("data", None)),
    "b": NamedSharding(mesh_b, P(None)),
}
restored = ckpt.restore("/tmp/elastic_ckpt", 3, like=tree, shardings=new_sh)
ok_vals = bool(jnp.all(restored["w"] == tree["w"]) and
               jnp.all(restored["b"] == tree["b"]))
ok_shard = (restored["w"].sharding.spec == P("data", None))
print(json.dumps({"values": ok_vals, "resharded": bool(ok_shard)}))
"""


@pytest.mark.slow
def test_elastic_remesh_restore():
    """A checkpoint written under mesh (2,4) restores onto mesh (8,) —
    shardings live in the runtime, never in the checkpoint."""
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    out = subprocess.run([sys.executable, "-c", ELASTIC_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600,
                         cwd=os.path.dirname(os.path.dirname(__file__)))
    assert out.returncode == 0, out.stderr[-2000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["values"] and res["resharded"]
