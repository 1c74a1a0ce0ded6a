"""Lowered StableHLO of the benchmark cells' train steps at the ``tiny-*``
shapes, on the CPU: the check that a refactor of the step factories left
the cells' programs as they were.

    python scripts/step_hlo.py <checkout> <out dir> [scan hetero dist scandist]

Writes ``<cell>.mlir`` (no location metadata) and ``<cell>.scopes`` (the
sorted name stacks that hold a ``glt.*`` scope, from the debug-info
text) for ``<checkout>``'s code.  Run it on ``git archive`` of the parent
and on the working tree, then ``cmp`` the files.
"""
import json
import os
import re
import sys

root, out_dir = os.path.abspath(sys.argv[1]), sys.argv[2]
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=4")
sys.path.insert(0, root)
os.chdir(root)
os.makedirs(out_dir, exist_ok=True)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from chipbench.common import Env  # noqa: E402
import importlib  # noqa: E402


def _json(*parts):
    with open(os.path.join(root, *parts)) as fh:
        return json.load(fh)


def env_of(config, traffic, chips):
    return Env(config=_json("chipbench", "configs", config + ".json"),
               traffic=_json("chipbench", "traffic", traffic + ".json"),
               seed=5, devices=jax.devices()[:chips], trace=False,
               log=lambda m: None)


def write(name, fn, *args):
    lowered = jax.jit(fn).lower(*args)
    # Scratch output of one process, compared after it ends.
    # gltlint: disable-next=non-atomic-state-publish
    with open(os.path.join(out_dir, name + ".mlir"), "w") as fh:
        fh.write(lowered.as_text())
    dbg = lowered.as_text(debug_info=True)
    stacks = re.findall(r'loc\("([^"]*glt\.[^"]*)"', dbg)
    # gltlint: disable-next=non-atomic-state-publish
    with open(os.path.join(out_dir, name + ".scopes"), "w") as fh:
        fh.write("\n".join(sorted(stacks)) + "\n")
    print(name, len(lowered.as_text().splitlines()), "lines,",
          len(stacks), "scoped locs", flush=True)


def driver(name, env):
    return importlib.import_module(f"chipbench.drivers.{name}").Driver(env)


which = sys.argv[3:] or ["scan", "hetero", "dist", "scandist"]
if "scan" in which:
    d = driver("scan_train", env_of("tiny-sage", "train-scan", 1))
    blk = jnp.zeros((d.group, d.batch), jnp.int32)
    write("train-scan", d.step, d.state, blk, jax.random.PRNGKey(0))
if "hetero" in which:
    d = driver("hetero_scan_train",
               env_of("tiny-rgat", "hetero-train-scan", 1))
    blk = jnp.zeros((d.group, d.batch), jnp.int32)
    write("hetero-train-scan", d.step, d.state, blk, jax.random.PRNGKey(0))
if "dist" in which or "scandist" in which:
    d = driver("dist_train", env_of("tiny-sage-dist4", "dist-train", 4))
    if "dist" in which:
        write("dist-train", d.step, d.state, jnp.asarray(d._seeds()),
              jax.random.PRNGKey(0))
    if "scandist" in which:
        import optax
        from glt_tpu.parallel.dist_train import make_scanned_dist_train_step
        sam = d.env.config["sampling"]
        sstep = make_scanned_dist_train_step(
            d.model, optax.adam(1e-3), d.d.graph, d.d.feature, d.d.labels,
            d.d.mesh, d.fanout, d.batch, frontier_cap=sam["frontier_cap"])
        blk = jnp.asarray(np.stack([d._seeds(), d._seeds()]))
        write("scanned-dist", sstep, d.state, blk, jax.random.PRNGKey(0))
