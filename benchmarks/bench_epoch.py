"""End-to-end epoch-time benchmarks for the BASELINE.md target configs.

Runs the actual example scripts (the same code a user would run) as
subprocesses and captures the LAST epoch line (first epochs pay compile),
emitting one JSON line per config:

  {"metric": "epoch_time:<config>", "value": seconds, "unit": "s",
   "subgraphs_per_s": ..., "loss": ...}

Configs map to BASELINE.md "Target configs":
  1. products   — supervised GraphSAGE, NeighborLoader       (config 1)
  2. ppi        — unsupervised GraphSAGE + negative sampling (config 2)
  3. seal       — SEAL link prediction, subgraph sampling    (config 3)
  4. igbh       — hetero R-GAT, HeteroNeighborLoader         (config 4)

Scales are synthetic-data fractions chosen so a run finishes in
minutes; they are recorded in the JSON so numbers are comparable across
rounds.  The parent never imports JAX, and the children run one after
another, so each holds the chip alone.  A child that fails is reported
as an ``{"error": ...}`` line and the exit code is 1.  Usage:

    python benchmarks/bench_epoch.py [--configs products ppi ...]
"""
import argparse
import json
import os
import re
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CONFIGS = {
    "products": {
        "cmd": [sys.executable, "examples/train_sage_products.py",
                "--scale", "0.05", "--epochs", "2"],
        "scale": 0.05,
    },
    "ppi": {
        "cmd": [sys.executable, "examples/graph_sage_unsup_ppi.py",
                "--scale", "0.5", "--epochs", "2"],
        "scale": 0.5,
    },
    "seal": {
        "cmd": [sys.executable, "examples/seal_link_pred.py",
                "--epochs", "2"],
        "scale": 1.0,
    },
    "igbh": {
        "cmd": [sys.executable, "examples/rgat_igbh.py",
                "--scale", "0.1", "--epochs", "2"],
        "scale": 0.1,
    },
    # Config 4 multi-chip (IGBH R-GAT distributed) on the 8-virtual-device
    # CPU mesh: fused hetero step over per-edge-type sharded CSRs.
    "igbh_dist_cpu8": {
        "cmd": [sys.executable, "examples/rgat_igbh.py",
                "--distributed", "8", "--scale", "0.5", "--epochs", "2"],
        "scale": 0.5,
        "env": {"JAX_PLATFORMS": "cpu",
                "XLA_FLAGS": "--xla_force_host_platform_device_count=8"},
    },
    # Config 5 (papers100M distributed) on the 8-virtual-device CPU mesh:
    # exercises the full partition -> DistDataset.load -> tiered-pipeline
    # path; wall-clock here characterises the code path, not TPU speed.
    "papers100m_cpu8": {
        "cmd": [sys.executable, "examples/dist_train_papers100m.py",
                "--devices", "8", "--scale", "2e-5", "--epochs", "2"],
        "scale": 2e-5,
        "env": {"JAX_PLATFORMS": "cpu",
                "XLA_FLAGS": "--xla_force_host_platform_device_count=8"},
    },
}

EPOCH_RE = re.compile(
    r"epoch (\d+): loss=([\d.naninf-]+)(?: acc=([\d.naninf-]+))?"
    r" time=([\d.]+)s(?: subgraphs/s=([\d.]+))?")


def run_config(name: str, cfg: dict, timeout: float) -> dict:
    out = {"metric": f"epoch_time:{name}", "unit": "s",
           "scale": cfg["scale"]}
    env = None
    if cfg.get("env"):
        env = dict(os.environ, **cfg["env"])
    try:
        proc = subprocess.run(
            cfg["cmd"], cwd=REPO, capture_output=True, text=True,
            timeout=timeout, env=env)
    except subprocess.TimeoutExpired:
        out["error"] = f"timeout after {timeout:.0f}s"
        return out
    matches = EPOCH_RE.findall(proc.stdout)
    if proc.returncode != 0 or not matches:
        out["error"] = (proc.stderr.strip().splitlines() or ["no output"])[-1]
        return out
    _, loss, acc, secs, sg = matches[-1]
    out["value"] = float(secs)
    out["loss"] = float(loss)
    if acc:
        out["acc"] = float(acc)
    if sg:
        out["subgraphs_per_s"] = float(sg)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--configs", nargs="+", default=list(CONFIGS),
                    choices=list(CONFIGS))
    ap.add_argument("--timeout", type=float, default=1800.0)
    args = ap.parse_args()
    failed = False
    for name in args.configs:
        out = run_config(name, CONFIGS[name], args.timeout)
        failed = failed or "error" in out
        print(json.dumps(out), flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
