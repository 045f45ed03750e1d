"""Four-chip check: the trainer over a real mesh, against one chip.

    python chip_mesh.py          # on a host with four TPU chips, one process

GPT-2 124M (``GPTConfig()``) through ``Model.prepare(adam, strategy)`` +
``Model.fit`` at T=1024, a few steps on a repeated batch, under

    one chip (the reference)  ·  dp=4  ·  tp=2 x dp=2

all in this one process (one process drives every chip of its host). For
each mesh it checks what a CPU dry run cannot: the compiled per-device
module calls the flash kernel on the LOCAL [B/dp * H/tp, T, D] operands
(not an all-gathered global one); parameters, Adam slots and the batch
are spread over the devices as their specs say; and the losses agree
with the one-chip run on the same batch. Exits non-zero on any failure
or without four TPU devices; the last line of stdout is one JSON object.
"""
import json
import os
import re
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np

from chip_smoke import T, fit_gpt2_124m, pallas_call_lines, step_hbm_gb

B, STEPS = 8, 3
# The O2 (pure bf16) step reports its loss in bf16: one ulp near 10 is
# 0.0625, i.e. 0.6% — so "agrees" means within about one ulp. (The CPU
# dry run's 2e-3, __graft_entry__.py, is for a float32 loss.)
LOSS_RTOL = 1e-2


def flash_operand_shapes(hlo_text):
    """First-output shapes of the Pallas Mosaic calls in a compiled
    per-device module, e.g. [[24, 1024, 64], ...]."""
    return [[int(v) for v in
             re.search(r"= \(?\w+\[([\d,]+)\]", line).group(1).split(",")]
            for line in pallas_call_lines(hlo_text)]


def spread(tree, n_dev):
    """(leaves whose shards do not cover all n_dev devices, leaves that
    are split — some shard smaller than the array)."""
    import jax

    bad, split = [], 0
    for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
        if not isinstance(x, jax.Array):
            continue
        shards = x.addressable_shards
        if len({s.device for s in shards}) != n_dev:
            bad.append(jax.tree_util.keystr(path))
        split += any(s.data.shape != x.shape for s in shards)
    return bad, split


def run(mesh_name, dp, tp, devices):
    from paddle_tpu.models import GPTConfig

    cfg = GPTConfig()
    t0 = time.time()
    model, losses = fit_gpt2_124m(B, STEPS, devices=devices, dp=dp, tp=tp)
    prog = model._dist_prog
    n_dev = len(devices)
    out = {"mesh": mesh_name, "mesh_shape": dict(prog.mesh.shape),
           "losses": [round(v, 5) for v in losses], "problems": []}
    shapes = flash_operand_shapes(prog._aot.as_text())
    local = [B // dp * cfg.heads // tp, T, cfg.head_dim]
    out["flash_call_shapes"] = shapes
    if not shapes or any(sh != local for sh in shapes):
        out["problems"].append(
            f"flash operands {shapes} are not the local {local}")
    for name, tree in (("params", prog.params), ("adam", prog.opt_state)):
        bad, split = spread(tree, n_dev)
        out[f"{name}_split_leaves"] = split
        if bad:
            out["problems"].append(f"{name} not on every device: {bad[:3]}")
        if tp > 1 and not split:
            out["problems"].append(f"{name}: nothing is tp-sharded")
    batch = prog._put_data(np.zeros((B, T), np.int32))
    shard_shapes = sorted({tuple(sh.data.shape)
                           for sh in batch.addressable_shards})
    out["batch_shard_shapes"] = [list(v) for v in shard_shapes]
    if shard_shapes != [(B // dp, T)] or \
            len({sh.device for sh in batch.addressable_shards}) != n_dev:
        out["problems"].append(f"batch shards {shard_shapes}")
    if tp > 1:
        qkv = [k for k in prog.params if "qkv.weight" in k][0]
        out["qkv_spec"] = str(prog.params[qkv].sharding.spec)
    out["per_device_step_gb"] = step_hbm_gb(prog._aot)
    out["wall_s"] = round(time.time() - t0, 1)
    return out


def main():
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) != 4:
        print(f"chip_mesh: needs four TPU devices, jax.devices() reports "
              f"{len(devs)} x {devs[0].platform!r}", file=sys.stderr)
        return 3
    results = [run("one chip", 1, 1, devs[:1]),
               run("dp=4", 4, 1, devs),
               run("tp=2 x dp=2", 2, 2, devs)]
    ref = np.asarray(results[0]["losses"])
    for r in results:
        got = np.asarray(r["losses"])
        if len(got) != STEPS or not np.isfinite(got).all() \
                or not got[-1] < got[0]:
            r["problems"].append(f"losses {r['losses']}")
        r["loss_rel_delta"] = float(np.max(np.abs(got - ref) / ref))
        if r["loss_rel_delta"] > LOSS_RTOL:
            r["problems"].append(
                f"loss differs from one chip by {r['loss_rel_delta']:.1e}")
        print("MESH", json.dumps(r), flush=True)
    ok = not any(r["problems"] for r in results)
    summary = {"ok": ok, "device": {"platform": devs[0].platform,
                                    "kind": devs[0].device_kind,
                                    "count": len(devs)},
               "meshes": results}
    if os.path.isdir("chiprun_out"):
        with open("chiprun_out/chip_mesh.json", "w") as f:
            json.dump(summary, f, indent=1)
    print(json.dumps({"ok": ok, "device": summary["device"],
                      "loss_rel_delta": {r["mesh"]: r["loss_rel_delta"]
                                         for r in results}}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
