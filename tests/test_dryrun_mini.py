"""Dry-run machinery regression: lower+compile real cells on a small mesh.

Uses an 8-device (2,4)=(data,model) mesh in a subprocess (device count is
process-global) with reduced shapes — exercises sanitize_specs, sharded
train/prefill/decode step construction and the roofline analyzer on the
very code paths the 512-chip run uses.
"""

import os
import subprocess
import sys

import pytest

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_smoke
from repro.configs.registry import ShapeSpec, input_specs
from repro.launch.dryrun import sanitize_specs, _batch_specs, _ns
from repro.models import build_model
from repro.roofline.analysis import analyze
from repro.serve import make_serve_step
from repro.train import TrainConfig, make_train_step

mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
arch = "%s"
cfg = get_smoke(arch)
cfg = dataclasses.replace(cfg, act_spec=(("data",), "model", None))
if cfg.family == "moe":
    cfg = dataclasses.replace(cfg, ep_axis="model")
model = build_model(cfg)

# ---- train cell
shape = ShapeSpec("mini_train", 64, 8, "train")
pa = model.abstract(jnp.float32)
ps = sanitize_specs(mesh, model.specs(), pa)
oa = {"m": pa, "v": pa, "step": jax.ShapeDtypeStruct((), jnp.int32)}
os_ = {"m": ps, "v": ps, "step": P()}
ba = input_specs(cfg, shape)
bs = _batch_specs(ba, ("data",))
step = make_train_step(model, TrainConfig())
j = jax.jit(step, in_shardings=(_ns(mesh, ps), _ns(mesh, os_), _ns(mesh, bs)),
            out_shardings=(_ns(mesh, ps), _ns(mesh, os_),
                           _ns(mesh, jax.tree.map(lambda _: P(),
                               {"loss": 0, "grad_norm": 0, "lr": 0}))))
with mesh:
    c = j.lower(pa, oa, ba).compile()
cell = analyze(arch, "mini_train", "mini", 8, c, 6.0 * model.n_params() * 512)
assert cell.flops > 0 and cell.bytes_accessed > 0
assert cell.bottleneck in ("compute", "memory", "collective")

# ---- decode cell
dshape = ShapeSpec("mini_decode", 64, 8, "decode")
cfg2 = dataclasses.replace(cfg, act_spec=None,
                           score_spec=(("data",), None, None, "model"))
model2 = build_model(cfg2)
pa2 = model2.abstract(jnp.bfloat16)
ps2 = sanitize_specs(mesh, model2.specs(), pa2)
ca = model2.abstract_cache(8, 64, jnp.bfloat16)
cs = sanitize_specs(mesh, model2.cache_specs(
    8, 64, extra_rules={"batch": ("data",), "seq": "model",
                        "kv_heads": None, "heads": None}), ca)
da = input_specs(cfg2, dshape)
ds = _batch_specs(da, ("data",))
sstep = make_serve_step(model2)
j2 = jax.jit(sstep, in_shardings=(_ns(mesh, ps2), _ns(mesh, cs), _ns(mesh, ds)),
             out_shardings=(NamedSharding(mesh, P(("data",))), _ns(mesh, cs)),
             donate_argnums=(1,))
with mesh:
    c2 = j2.lower(pa2, ca, da).compile()
print("MINI_DRYRUN_OK")
"""


@pytest.mark.parametrize("arch", ["smollm-360m", "gemma3-1b",
                                  "deepseek-moe-16b", "mamba2-2.7b",
                                  "zamba2-1.2b"])
def test_mini_dryrun_compiles(arch):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    r = subprocess.run([sys.executable, "-c", _SCRIPT % arch],
                       capture_output=True, text=True, env=env, timeout=900)
    assert "MINI_DRYRUN_OK" in r.stdout, r.stdout[-2000:] + r.stderr[-3000:]
