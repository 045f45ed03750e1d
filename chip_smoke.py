"""On-chip smoke: the trainer and the decode server, end to end, on one TPU.

    python chip_smoke.py

Drives the two main paths once through the entry points a user calls, at
GPT-2 124M width (``GPTConfig()``: vocab 50,304, hidden 768, 12 layers,
12 heads, T=1024), random weights from a seed:

1. trainer  — ``Model.prepare(adam, strategy)`` + ``Model.fit`` (what
   chipbench's train cell times), a few steps on a repeated batch;
2. artifact — ``decode.save_for_decode`` plus a float32 full-forward
   greedy oracle for a handful of fixed prompts;
3. server   — ``python -m paddle_tpu.inference.serve <prefix> --decode
   --warmup`` with the default slot count, the prompts sent over a
   socket, then SIGTERM and a clean drain.

Each phase is a child process of its own and this parent never
initialises a JAX backend: a chip belongs to one process at a time. Any
phase that fails, times out or finds no TPU makes the exit code non-zero
and no result line is printed. On success stdout carries one line per
phase (TRAINER / ARTIFACT / SERVER / SUMMARY + JSON) and its last line is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

It does not "smoke on CPU": ``JAX_PLATFORMS=cpu python chip_smoke.py``
exits non-zero saying there is no TPU.
"""
import argparse
import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

NO_TPU_RC = 3                  # a child's exit code for "JAX found no TPU"
T = 1024
TRAIN_STEPS = 8
MAX_NEW = 8
# A server token that differs from the float32 oracle's is a near-tie
# flipped by the chip's default (bf16-pass) matmul precision when the
# oracle's own top-2 logit gap there is below this; above it, it is a
# wrong page, position or weight. Random-init GPT-2 logits have a
# standard deviation near 0.55 and a typical top-2 gap near 0.1; 12
# layers of bf16-pass matmuls move a logit by a few 1e-2.
MARGIN_TOL = 0.08
PHASE_TIMEOUT = {"trainer": 600, "artifact": 420, "server_start": 900}


# ---------------------------------------------------------------------------
# children (these touch JAX)
# ---------------------------------------------------------------------------

def _require_tpu():
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU — jax.devices() reports "
              f"{devs[0].platform!r} ({len(devs)} device(s))",
              file=sys.stderr, flush=True)
        sys.exit(NO_TPU_RC)
    return devs


def _device_json(devs):
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def _on_tpu(tree):
    """Every array leaf of `tree` lives on TPU devices only."""
    import jax

    leaves = [x for x in jax.tree_util.tree_leaves(tree)
              if isinstance(x, jax.Array)]
    return bool(leaves) and all(
        d.platform == "tpu" for x in leaves for d in x.devices())


def pallas_call_lines(hlo_text):
    """The Pallas Mosaic custom-call lines of a compiled module's text."""
    return [ln for ln in hlo_text.splitlines()
            if 'custom_call_target="tpu_custom_call"' in ln
            and "pallas_call" in ln]


def _flash_calls(hlo_text):
    """(forward, backward) counts of the flash-attention Mosaic calls:
    the backward ones are traced under transpose(jvp)."""
    lines = pallas_call_lines(hlo_text)
    bwd = sum("transpose(" in ln.split('op_name="', 1)[-1][:200]
              for ln in lines)
    return len(lines) - bwd, bwd


def fit_gpt2_124m(B, steps, devices=None, dp=1, tp=1):
    """What chipbench's train cell times, minus the timing: GPT-2 124M
    (``GPTConfig()``) through ``Model.prepare(adam, strategy)`` (AMP O2, Adam) and
    ``Model.fit`` for `steps` steps at T tokens on ONE batch of B
    sequences repeated — so the loss must come down. `devices`/`dp`/`tp`
    pick the mesh (default: every device, data parallel). Returns
    (model, per-step losses); the compiled step is
    ``model._dist_prog``."""
    import numpy as np

    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    import paddle_tpu.optimizer as opt
    from paddle_tpu.distributed.fleet import DistributedStrategy
    from paddle_tpu.hapi import Model, callbacks as hapi_cbks
    from paddle_tpu.io import TensorDataset
    from paddle_tpu.models import GPT, GPTConfig
    from paddle_tpu.static import InputSpec

    cfg = GPTConfig()

    class _LMLoss(nn.Layer):
        """forward(ids, labels) -> the GPT's fused-head LM loss; the
        tensor-parallel spec protocol is delegated to the GPT."""

        def __init__(self, m):
            super().__init__()
            self.m = m

        def forward(self, ids, labels):
            return self.m.loss(ids, labels)

        def param_shardings(self, params, mesh_axis_tp="tp"):
            inner = self.m.param_shardings(
                {k[len("m."):]: v for k, v in params.items()},
                mesh_axis_tp=mesh_axis_tp)
            return {"m." + k: spec for k, spec in inner.items()}

    class _Losses(hapi_cbks.Callback):
        def __init__(self):
            super().__init__()
            self.losses = []

        def on_train_batch_end(self, step, logs=None):
            self.losses.append(float(logs["loss"]))

    paddle.seed(0)
    net = _LMLoss(GPT(cfg))
    net.train()
    model = Model(net, inputs=[InputSpec([None, T], "int32"),
                               InputSpec([None, T], "int32")])
    s = DistributedStrategy()
    s.amp = True
    s.amp_configs.use_pure_bf16 = True
    if tp > 1:
        s.tensor_parallel = True
        s.hybrid_configs.mp_degree = tp
        s.hybrid_configs.dp_degree = dp
    s.build_mesh(devices=devices)     # the mesh Model.prepare picks up
    adam = opt.Adam(learning_rate=3e-4, parameters=model.parameters())
    model.prepare(adam, strategy=s)
    rng = np.random.default_rng(0)
    ids = rng.integers(0, cfg.vocab_size, (B, T), dtype=np.int32)
    labels = np.concatenate([ids[:, 1:], ids[:, :1]], axis=1)
    rec = _Losses()
    model.fit(TensorDataset([np.tile(ids, (steps, 1)),
                             np.tile(labels, (steps, 1))]),
              batch_size=B, epochs=1, verbose=0, shuffle=False, log_freq=1,
              callbacks=[rec])
    return model, rec.losses


def step_hbm_gb(compiled):
    """HBM one execution of a compiled step needs, from the compiler."""
    m = compiled.memory_analysis()
    return round((m.argument_size_in_bytes + m.output_size_in_bytes
                  - m.alias_size_in_bytes + m.temp_size_in_bytes) / 1e9, 2)


def phase_trainer(out_path):
    devs = _require_tpu()
    import jax
    import numpy as np

    from paddle_tpu import profiler

    t0 = time.time()
    B, tried = 16, []
    while True:
        try:
            model, losses = fit_gpt2_124m(B, TRAIN_STEPS)
            break
        except jax.errors.JaxRuntimeError as e:
            if "RESOURCE_EXHAUSTED" not in str(e) or B == 1:
                raise
            tried.append({"B": B, "error": str(e).splitlines()[0][:300]})
            B //= 2
    prog = model._dist_prog
    fwd, bwd = _flash_calls(prog._aot.as_text())
    events = profiler.compile_events()
    out = {
        "device": _device_json(devs),
        "B": B, "T": T, "did_not_fit": tried, "steps": len(losses),
        "losses": [round(v, 4) for v in losses],
        "flash_calls": {"forward": fwd, "backward": bwd},
        "state_on_tpu": {"params": _on_tpu(prog.params),
                         "optimizer": _on_tpu(prog.opt_state)},
        "step_hbm_gb": step_hbm_gb(prog._aot),
        "compile_s": round(sum(e["compile_s"] for e in events), 1),
        "compile_cache": sorted({e["cache"] for e in events}),
        "peak_hbm_gb": round((devs[0].memory_stats() or {}).get(
            "peak_bytes_in_use", 0) / 1e9, 2),
        "wall_s": round(time.time() - t0, 1),
    }
    problems = []
    if not all(np.isfinite(losses)) or len(losses) != TRAIN_STEPS:
        problems.append(f"losses not finite / wrong count: {losses}")
    elif not losses[-1] < losses[0]:
        problems.append(f"loss did not come down on a repeated batch: "
                        f"{losses}")
    if fwd < 1 or bwd < 1:
        problems.append(f"compiled step lacks the flash-attention Mosaic "
                        f"calls (forward={fwd}, backward={bwd})")
    if not all(out["state_on_tpu"].values()):
        problems.append(f"train state not on TPU: {out['state_on_tpu']}")
    out["problems"] = problems
    with open(out_path, "w") as f:
        json.dump(out, f)
    sys.exit(1 if problems else 0)


def smoke_prompts(vocab):
    """Fixed prompts: short; two sharing a page-aligned 32-token head
    (2 pages of 16) with distinct tails; one near 1k tokens."""
    import numpy as np

    rng = np.random.default_rng(1234)
    draw = lambda n: [int(t) for t in rng.integers(0, vocab, n)]
    head = draw(32)
    return {"short": draw(5), "shared_a": head + draw(8),
            "shared_b": head + draw(8), "long": draw(T - 2 * MAX_NEW - 8)}


def phase_artifact(out_path, prefix):
    devs = _require_tpu()
    import jax
    import jax.numpy as jnp
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu import framework
    from paddle_tpu.inference import decode
    from paddle_tpu.models import GPT, GPTConfig

    t0 = time.time()
    cfg = GPTConfig()
    paddle.seed(0)
    model = GPT(cfg)
    model.eval()
    decode.save_for_decode(model, prefix)

    # the oracle is a reference: no kernels, float32 at full precision
    paddle.set_flags({"use_pallas_attention": False})
    params = framework.param_arrays(model)
    prompts = smoke_prompts(cfg.vocab_size)
    names = list(prompts)

    @jax.jit
    def top2_at(p, ids, last):
        with jax.default_matmul_precision("highest"):
            logits, _ = framework.functional_call(model, p, {}, ids)
        row = jnp.take_along_axis(
            logits, last[:, None, None], axis=1)[:, 0].astype(jnp.float32)
        vals, idx = jax.lax.top_k(row, 2)
        return idx[:, 0], vals[:, 0] - vals[:, 1]

    seqs = [list(prompts[n]) for n in names]
    toks = {n: [] for n in names}
    margins = {n: [] for n in names}
    for _ in range(MAX_NEW):
        ids = np.zeros((len(seqs), T), np.int32)
        for i, s in enumerate(seqs):
            ids[i, :len(s)] = s
        last = np.asarray([len(s) - 1 for s in seqs], np.int32)
        nxt, gap = top2_at(params, ids, last)
        for i, n in enumerate(names):
            toks[n].append(int(nxt[i]))
            margins[n].append(round(float(gap[i]), 5))
            seqs[i].append(int(nxt[i]))
    out = {"device": _device_json(devs), "prompts": prompts,
           "oracle_tokens": toks, "oracle_margins": margins,
           "vocab_size": cfg.vocab_size,
           "param_bytes": int(sum(v.nbytes for v in params.values())),
           "wall_s": round(time.time() - t0, 1)}
    with open(out_path, "w") as f:
        json.dump(out, f)


# ---------------------------------------------------------------------------
# parent (never touches a JAX backend)
# ---------------------------------------------------------------------------

class SmokeFailure(Exception):
    pass


def _tail(text, n=40):
    return "\n".join(text.splitlines()[-n:])


def _run_child(phase, out_path, *extra):
    cmd = [sys.executable, os.path.abspath(__file__), "--phase", phase,
           "--out", out_path, *extra]
    t0 = time.time()
    try:
        p = subprocess.run(cmd, capture_output=True, text=True,
                           timeout=PHASE_TIMEOUT[phase])
    except subprocess.TimeoutExpired as e:
        err = e.stderr.decode(errors="replace") \
            if isinstance(e.stderr, bytes) else (e.stderr or "")
        raise SmokeFailure(f"phase {phase}: timed out after "
                           f"{PHASE_TIMEOUT[phase]}s\n{_tail(err)}")
    result = None
    if os.path.exists(out_path):
        with open(out_path) as f:
            result = json.load(f)
    if p.returncode == NO_TPU_RC:
        raise SmokeFailure(f"no TPU: {_tail(p.stderr, 3)}")
    if p.returncode != 0:
        raise SmokeFailure(
            f"phase {phase}: rc={p.returncode} "
            f"problems={(result or {}).get('problems')}\n{_tail(p.stderr)}")
    result["phase_wall_s"] = round(time.time() - t0, 1)
    return result


def _get(port, path):
    with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}",
                                timeout=30) as r:
        return r.read().decode()


def _metric(text, name):
    """Sum of the samples of one family in a Prometheus exposition."""
    total = 0.0
    for line in text.splitlines():
        if line.startswith(name) and line[len(name):len(name) + 1] in " {":
            total += float(line.rsplit(" ", 1)[1])
    return total


def _hbm_problems(device_memory, resident_bytes):
    """Where the weights and KV pools actually live: /statusz's
    device_memory must be TPU HBM holding at least their bytes."""
    if not device_memory or not all(
            k.lower().startswith("tpu") and v.get("bytes_limit", 0) > 0
            for k, v in device_memory.items()):
        return [f"/statusz device_memory is not TPU HBM: {device_memory}"]
    in_use = max(v.get("bytes_in_use", 0) for v in device_memory.values())
    if in_use < resident_bytes:
        return [f"HBM in use {in_use} < params + KV pools "
                f"{resident_bytes}: they are not on the chip"]
    return []


def _serve(prefix, oracle, slots):
    from paddle_tpu.inference import serve

    cmd = [sys.executable, "-m", "paddle_tpu.inference.serve", prefix,
           "--decode", "--warmup", "--port", "0", "--metrics-port", "0",
           "--decode-max-new", str(MAX_NEW), "--stats-interval", "0"]
    if slots:
        cmd += ["--decode-slots", str(slots)]
    env = dict(os.environ, PYTHONPATH=HERE + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    t0 = time.time()
    proc = subprocess.Popen(cmd, stdout=subprocess.PIPE,
                            stderr=subprocess.PIPE, text=True, env=env)
    lines, errs, ports = [], [], {}
    serving = threading.Event()

    def read_out():
        for line in proc.stdout:
            lines.append(line.rstrip("\n"))
            word = line.split()
            if word and word[0] in ("METRICS", "SERVING"):
                ports[word[0]] = int(word[1])
            if word and word[0] == "SERVING":
                serving.set()
        serving.set()                       # EOF

    readers = [threading.Thread(target=read_out, daemon=True),
               threading.Thread(
                   target=lambda: errs.extend(proc.stderr), daemon=True)]
    for r in readers:
        r.start()

    def fail(msg):
        raise SmokeFailure(f"phase server: {msg}\nstdout tail:\n"
                           + _tail("\n".join(lines), 15)
                           + "\nstderr tail:\n" + _tail("".join(errs)))

    try:
        if not serving.wait(PHASE_TIMEOUT["server_start"]) \
                or "SERVING" not in ports:
            fail(f"no SERVING line (rc={proc.poll()})")
        cold_s = round(time.time() - t0, 1)
        warm = [ln for ln in lines if ln.startswith("WARMUP ")]
        if "METRICS" not in ports or not warm:
            fail("missing METRICS / WARMUP line")
        warmup_compiles = int(warm[0].split("compiles=")[1])

        st0 = json.loads(_get(ports["METRICS"], "/statusz"))
        m0 = _get(ports["METRICS"], "/metrics")
        problems = []
        vocab = oracle["vocab_size"]
        order = ["short", "shared_a", "shared_b", "long", "shared_a"]
        replies, agreement = {}, {}
        t_req = time.time()
        for name in order:
            seqs = []
            with socket.create_connection(("127.0.0.1", ports["SERVING"]),
                                          timeout=300) as sock:
                toks = serve.decode_request(
                    sock, oracle["prompts"][name],
                    opts={"max_new_tokens": MAX_NEW, "temperature": 0.0},
                    on_token=lambda t, ctx: seqs.append(ctx.get("seq")))
            if len(toks) != MAX_NEW or \
                    not all(0 <= t < vocab for t in toks):
                problems.append(f"{name}: bad reply {toks}")
            if seqs != list(range(len(seqs))) or len(seqs) != MAX_NEW:
                problems.append(f"{name}: token frames not gapless: {seqs}")
            if name in replies and replies[name] != toks:
                problems.append(f"{name}: same prompt, different tokens: "
                                f"{replies[name]} vs {toks}")
            replies[name] = toks
            want = oracle["oracle_tokens"][name]
            same = 0
            while same < MAX_NEW and toks[same:same + 1] == \
                    want[same:same + 1]:
                same += 1
            agreement[name] = f"{same}/{MAX_NEW}"
            if same < MAX_NEW:
                gap = oracle["oracle_margins"][name][same]
                agreement[name] += f" (oracle top-2 gap {gap} at {same})"
                if gap > MARGIN_TOL:
                    problems.append(
                        f"{name}: token {same} is {toks[same]}, oracle "
                        f"{want[same]} with top-2 gap {gap} > {MARGIN_TOL}")
        req_s = round(time.time() - t_req, 1)

        st1 = json.loads(_get(ports["METRICS"], "/statusz"))
        m1 = _get(ports["METRICS"], "/metrics")
        hits = _metric(m1, "paddle_tpu_decode_prefix_hits_total")
        if hits < 2:        # shared_b on shared_a's head, shared_a again
            problems.append(f"prefix hits {hits} < 2")
        if st1["compiles"] != st0["compiles"] or \
                _metric(m1, "paddle_tpu_compile_total") != \
                _metric(m0, "paddle_tpu_compile_total"):
            problems.append(f"compiled after warm-up: {st0['compiles']} -> "
                            f"{st1['compiles']}")
        dec = st1["decode"]
        pool_bytes = dec["pages"]["pages_total"] * dec["kv_page_bytes"]
        in_use = max([v.get("bytes_in_use", 0)
                      for v in st1["device_memory"].values()] or [0])
        problems += _hbm_problems(st1["device_memory"],
                                  oracle["param_bytes"] + pool_bytes)

        proc.send_signal(signal.SIGTERM)
        rc = proc.wait(timeout=120)
        for r in readers:
            r.join(timeout=10)
        if rc != 0 or "DRAINED ok=True" not in lines:
            problems.append(f"drain: rc={rc}, tail={lines[-3:]}")
        if problems:
            fail("; ".join(problems))
        return {"slots": dec["max_slots"], "slots_given": slots or None,
                "batch_ladder": dec["batch_ladder"],
                "warmup_executables": warmup_compiles,
                "compiles_total": st1["compiles"],
                "compile_cache": st1["compile_cache"],
                "compile_s": st1["compile_seconds"],
                "start_s": cold_s, "requests": len(order),
                "requests_s": req_s, "prefix_hits": int(hits),
                "oracle_agreement": agreement,
                "kv_pool_gb": round(pool_bytes / 1e9, 2),
                "hbm_in_use_gb": round(in_use / 1e9, 2)}
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=30)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--phase", choices=("trainer", "artifact"))
    ap.add_argument("--out")
    ap.add_argument("--prefix")
    ap.add_argument("--decode-slots", type=int, default=0,
                    help="pass --decode-slots to the server instead of "
                         "letting it size its pool from HBM")
    args = ap.parse_args(argv)
    if args.phase == "trainer":
        return phase_trainer(args.out)
    if args.phase == "artifact":
        return phase_artifact(args.out, args.prefix)

    t0 = time.time()
    work = tempfile.mkdtemp(prefix="chip_smoke_")
    try:
        trainer = _run_child("trainer", os.path.join(work, "trainer.json"))
        print("TRAINER", json.dumps(trainer), flush=True)
        prefix = os.path.join(work, "gpt2_124m")
        oracle = _run_child("artifact", os.path.join(work, "oracle.json"),
                            "--prefix", prefix)
        print("ARTIFACT", json.dumps(
            {k: oracle[k] for k in ("oracle_tokens", "oracle_margins",
                                    "wall_s", "phase_wall_s")}), flush=True)
        t1 = time.time()
        server = _serve(prefix, oracle, args.decode_slots)
        server["phase_wall_s"] = round(time.time() - t1, 1)
        print("SERVER", json.dumps(server), flush=True)
    except SmokeFailure as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    finally:
        shutil.rmtree(work, ignore_errors=True)
    verdicts = set(trainer["compile_cache"]) | set(server["compile_cache"])
    print("SUMMARY", json.dumps({
        "trainer": {k: trainer[k] for k in ("B", "steps", "losses")},
        "server": {k: server[k] for k in ("slots", "warmup_executables",
                                          "requests", "prefix_hits")},
        # "hit" only when every compile of every phase was a cache hit
        "compile_cache": "hit" if verdicts == {"hit"} else
                         "miss" if "miss" in verdicts else "off",
        "wall_s": round(time.time() - t0, 1)}), flush=True)
    print(json.dumps({"ok": True, "device": trainer["device"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
