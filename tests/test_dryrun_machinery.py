"""CI-sized dry-run: the full lowering machinery (specs, meshes, roofline
extraction) on a reduced arch with 8 host devices in a subprocess — proves
the launch stack without the 512-device production sweep."""
import os
import subprocess
import sys

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses
import jax
import numpy as np
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

from repro.configs import ARCHS, reduced
from repro.configs.base import ShapeConfig
from repro.models import build_model, input_specs
from repro.roofline.analysis import collective_bytes, roofline_terms
from repro.sharding.ctx import configure
from repro.sharding.specs import batch_specs, cache_specs, tree_param_specs
from repro.train.optimizer import adamw_init
from repro.train.step import make_train_step

mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                     axis_types=(AxisType.Auto,) * 3)
configure(mesh)
cfg = dataclasses.replace(reduced(ARCHS["qwen2.5-3b"]), num_heads=4,
                          kv_heads=2)
model = build_model(cfg, tp=2)
shape = ShapeConfig("t", "train", 32, 8)

params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
p_specs = tree_param_specs(params, tp=2, dsize=4)
opt = jax.eval_shape(adamw_init, params)
state = {"params": params, "opt": opt}
s_specs = {"params": p_specs, "opt": {"m": p_specs, "v": p_specs,
                                      "step": P()}}
batch = input_specs(cfg, shape)
b_specs = batch_specs(("pod", "data"), cfg, shape)

ns = lambda t: jax.tree.map(lambda s: NamedSharding(mesh, s), t,
                            is_leaf=lambda x: isinstance(x, P))
step = make_train_step(model, microbatches=2)
lowered = jax.jit(step, in_shardings=(ns(s_specs), ns(b_specs)),
                  out_shardings=(ns(s_specs),
                                 ns({"loss": P(), "gnorm": P(),
                                     "lr": P()}))).lower(state, batch)
compiled = lowered.compile()
ca = compiled.cost_analysis()
if isinstance(ca, (list, tuple)):
    ca = ca[0]
cb = collective_bytes(compiled.as_text())
assert ca["flops"] > 0
assert cb["total"] > 0, "multi-axis mesh must produce collectives"
terms = roofline_terms(ca["flops"] * 8, ca["bytes accessed"] * 8,
                       cb["total"], chips=8)
assert terms["dominant"] in ("compute", "memory", "collective")
print("DRYRUN_MACHINERY_OK", cb["counts"])
"""


def test_dryrun_machinery_small_mesh():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=900)
    assert "DRYRUN_MACHINERY_OK" in out.stdout, out.stdout + out.stderr
