"""Global flag registry.

TPU-native analog of the gflags tier in
/root/reference/paddle/fluid/platform/flags.cc (33 DEFINE sites) and the
python getter/setter bridge /root/reference/paddle/fluid/pybind/
global_value_getter_setter.cc. A single-process registry: flags are defined
with a default + doc, overridable from the environment (FLAGS_xxx) and from
`set_flags`, read with `get_flags`.

XLA-level knobs are forwarded by appending to XLA_FLAGS before first device
use; everything else is framework-local.
"""
from __future__ import annotations

import os
import threading
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional

from .errors import NotFoundError


@dataclass
class _Flag:
    name: str
    default: Any
    doc: str
    parser: Callable[[str], Any]
    value: Any = None
    on_change: Optional[Callable[[Any], None]] = None


_REGISTRY: Dict[str, _Flag] = {}
_LOCK = threading.Lock()


def _parse_bool(s):
    if isinstance(s, bool):
        return s
    return str(s).lower() in ("1", "true", "yes", "on")


def define_flag(name, default, doc="", parser=None, on_change=None):
    """DEFINE_xxx equivalent. Environment FLAGS_<name> overrides the default."""
    if parser is None:
        if isinstance(default, bool):
            parser = _parse_bool
        elif isinstance(default, int):
            parser = int
        elif isinstance(default, float):
            parser = float
        else:
            parser = str
    value = default
    env = os.environ.get("FLAGS_" + name)
    if env is not None:
        value = parser(env)
    with _LOCK:
        _REGISTRY[name] = _Flag(name, default, doc, parser, value, on_change)
    return value


def get_flags(flags):
    """paddle.get_flags parity: accepts a name or list of names."""
    single = isinstance(flags, str)
    names = [flags] if single else list(flags)
    out = {}
    for n in names:
        key = n[6:] if n.startswith("FLAGS_") else n
        if key not in _REGISTRY:
            raise NotFoundError(f"Flag {n!r} is not defined")
        out[n] = _REGISTRY[key].value
    return out[flags] if single else out


def set_flags(flags: Dict[str, Any]):
    """paddle.set_flags parity."""
    for n, v in flags.items():
        key = n[6:] if n.startswith("FLAGS_") else n
        if key not in _REGISTRY:
            raise NotFoundError(f"Flag {n!r} is not defined")
        f = _REGISTRY[key]
        f.value = f.parser(v) if isinstance(v, str) else v
        if f.on_change is not None:
            f.on_change(f.value)


def all_flags():
    return {n: f.value for n, f in _REGISTRY.items()}


# ---------------------------------------------------------------------------
# Env-flag catalog (PADDLE_TPU_* runtime knobs)
# ---------------------------------------------------------------------------
# Unlike FLAGS_* above (process-global, settable via set_flags), PADDLE_TPU_*
# knobs are read from the environment at their point of use so subprocesses
# (router backends, supervised workers) inherit them naturally.  The catalog
# makes every such knob declared, typed and documented in one place:
#
#   * tpulint rule TPL041 rejects direct os.environ reads of PADDLE_TPU_*
#     names anywhere outside this module;
#   * TPL042 rejects PADDLE_TPU_* names that are not defined here;
#   * TPL043 keeps docs/flags.md (generated by `python -m
#     paddle_tpu.core.flags`) in sync with this catalog.
#
# Call sites use env_value() for default+parse semantics, or env_raw() when
# the site needs the unparsed string (spec grammars, presence checks).


@dataclass
class _EnvFlag:
    name: str
    default: Any
    doc: str
    parser: Callable[[str], Any]


_ENV_REGISTRY: Dict[str, _EnvFlag] = {}


def define_env_flag(name, default, doc, parser=None):
    """Declare one PADDLE_TPU_* environment knob (name, default, doc)."""
    if parser is None:
        if isinstance(default, bool):
            parser = _parse_bool
        elif isinstance(default, int):
            parser = int
        elif isinstance(default, float):
            parser = float
        else:
            parser = str
    with _LOCK:
        _ENV_REGISTRY[name] = _EnvFlag(name, default, doc, parser)


def env_raw(name) -> Optional[str]:
    """Unparsed environment value for a catalogued flag (None when unset).

    For call sites with spec grammars or set/unset semantics the parser
    cannot express; everything else should use env_value().
    """
    if name not in _ENV_REGISTRY:
        raise NotFoundError(f"Env flag {name!r} is not in the catalog")
    return os.environ.get(name)


def env_value(name) -> Any:
    """Parsed value of a catalogued flag; unset/empty/unparsable -> default."""
    flag = _ENV_REGISTRY.get(name)
    if flag is None:
        raise NotFoundError(f"Env flag {name!r} is not in the catalog")
    raw = os.environ.get(name)
    if raw is None or not str(raw).strip():
        return flag.default
    try:
        return flag.parser(raw)
    except (ValueError, TypeError):
        return flag.default


def all_env_flags() -> Dict[str, Any]:
    """name -> default for every catalogued PADDLE_TPU_* flag."""
    return {n: f.default for n, f in sorted(_ENV_REGISTRY.items())}


def env_flags_markdown() -> str:
    """docs/flags.md content, generated from the catalog (TPL043 syncs it)."""
    lines = [
        "# Environment flags",
        "",
        "Every `PADDLE_TPU_*` runtime knob, generated from the env-flag",
        "catalog in `paddle_tpu/core/flags.py` — regenerate with",
        "`python -m paddle_tpu.core.flags > docs/flags.md`.",
        "Unset, empty or unparsable values fall back to the default.",
        "",
        "| Flag | Default | Meaning |",
        "|---|---|---|",
    ]
    for name, flag in sorted(_ENV_REGISTRY.items()):
        default = "(unset)" if flag.default is None else repr(flag.default)
        doc = " ".join(flag.doc.split())
        lines.append(f"| `{name}` | `{default}` | {doc} |")
    lines.append("")
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# Framework flags (the subset of platform/flags.cc that is meaningful on TPU,
# plus TPU-specific ones).
# ---------------------------------------------------------------------------
define_flag("check_nan_inf", False,
            "Scan op outputs for NaN/Inf in eager mode (flags.cc:44 analog).")
define_flag("benchmark", False, "Sync + time every eager op.")
define_flag("eager_delete_tensor_gb", 0.0,
            "Kept for API parity; XLA owns buffer lifetime on TPU.")
define_flag("allocator_strategy", "xla",
            "Kept for API parity; PJRT/XLA own device memory on TPU.")
define_flag("fraction_of_gpu_memory_to_use", 0.92,
            "Parity alias for per-chip HBM headroom fraction.")
define_flag("use_pallas_attention", True,
            "Use the Pallas flash-attention kernel when applicable.")
define_flag("pallas_attention_min_seq", 512,
            "Route sdpa to the flash kernel only at seq_len >= this; below "
            "it XLA's fused composition wins on-chip (measured crossover: "
            "at T=1024 flash is 3.9 ms vs 4.6 ms XLA per GPT-2 layer "
            "fwd+bwd on v5e, and the gap widens with T^2 above).")
define_flag("amp_dtype", "bfloat16",
            "Reduced precision dtype for AMP (bf16 is MXU native).")
define_flag("cudnn_deterministic", False,
            "Parity alias: forces deterministic reductions where we control them.")
define_flag("max_inplace_grad_add", 0,
            "Parity flag from flags.cc; unused (functional grads).")
define_flag("tpu_matmul_precision", "default",
            "jax.lax matmul precision: default|high|highest.")


# ---------------------------------------------------------------------------
# The PADDLE_TPU_* env-flag catalog.  Keep alphabetical; docs/flags.md is
# generated from this block and tpulint TPL042/TPL043 enforce the sync.
# ---------------------------------------------------------------------------
define_env_flag(
    "PADDLE_TPU_ASYNC_STEPS", 2,
    "Async step pipeline in-flight window (jit/async_pipeline.py). 0, 'off' "
    "or 'sync' disables async stepping; >=1 bounds dispatched-but-unfetched "
    "steps.")
define_env_flag(
    "PADDLE_TPU_CHAOS", "",
    "Deterministic fault-injection schedule for the chaos harness "
    "(testing/chaos.py), e.g. 'ckpt.write:2:OSError'. Unset disables "
    "injection. See docs/fault_tolerance.md for the grammar and the "
    "registered site table.")
define_env_flag(
    "PADDLE_TPU_DECODE_BUCKETS", "",
    "Decode-engine batch bucket ladder override, space/comma-separated "
    "ints (inference/decode.py); empty uses powers of two up to max_slots.")
define_env_flag(
    "PADDLE_TPU_DECODE_DRAFT_MODEL", "",
    "Draft-model artifact prefix for speculative decoding "
    "(inference/decode.py): a save_for_decode() prefix whose GPT shares "
    "the target's vocab. Empty disables speculation unless --draft-model "
    "is passed to serve.")
define_env_flag(
    "PADDLE_TPU_DECODE_DRAFT_QUANT", False,
    "Int8-quantize the speculative-decoding DRAFT weights at "
    "load_for_decode when the draft artifact is still fp32 "
    "(quant/ptq.py). Draft numerics only move the acceptance rate, "
    "never the target token stream.")
define_env_flag(
    "PADDLE_TPU_DECODE_HANDOFF", False,
    "Enable the decode engine's cross-process KV-page handoff endpoints "
    "for disaggregated prefill/decode serving (inference/decode.py "
    "export_kv/import_kv, docs/serving.md): a prefill worker ships a "
    "prompt's full KV pages to a decode worker, which admits the stream "
    "as a prefix-cache hit. Implied on by serve --role prefill/decode; "
    "enabling it implies the prefix cache.")
define_env_flag(
    "PADDLE_TPU_DECODE_HOST_PAGES", 0,
    "Host-RAM KV tier capacity in pages for the paged decode engine "
    "(memory/migration.py): under pool pressure cold pages spill to "
    "preallocated host arenas and refetch on demand, turning HBM into "
    "a cache over a much larger KV store. 0 disables tiering; enabling "
    "it implies the prefix cache (spills route through the trie).")
define_env_flag(
    "PADDLE_TPU_DECODE_KV_DTYPE", "float32",
    "Decode KV page-pool dtype: 'float32', or 'int8' for quantized "
    "pages (quant/kv.py) — int8 payload plus one fp32 scale per "
    "(token row, head), cutting page HBM ~4x at the cost of ~1/254 "
    "relative rounding error per K/V row.")
define_env_flag(
    "PADDLE_TPU_DECODE_PAGE_TOKENS", 16,
    "KV-cache page size in tokens for the paged decode engine "
    "(inference/decode.py, memory/page_allocator.py). Smaller pages "
    "pack ragged sequences tighter; larger pages shrink block tables.")
define_env_flag(
    "PADDLE_TPU_DECODE_PREEMPT", True,
    "Allow the decode engine to preempt the lowest-priority active slot "
    "to host when a strictly higher-priority request cannot be admitted "
    "(inference/decode.py). The victim's pages are released, its state "
    "stays host-side, and it resumes token-identically when pressure "
    "drops. Off = priorities only order the queue.")
define_env_flag(
    "PADDLE_TPU_DECODE_PREFIX_CACHE", True,
    "Enable copy-on-write prefix sharing in the paged decode engine: "
    "page-aligned prompt prefixes are cached in a hash trie and mapped "
    "(refcount++) into later requests with the same head. Disable to "
    "force every admission through a full prefill.")
define_env_flag(
    "PADDLE_TPU_DECODE_SPECULATE", 0,
    "Speculation depth k for draft-and-verify decoding "
    "(inference/decode.py): the draft model runs up to k greedy steps "
    "per scheduler tick and the target verifies k+1 positions in one "
    "forward. 0 (default) decodes one token per step; requires a draft "
    "model (PADDLE_TPU_DECODE_DRAFT_MODEL or --draft-model).")
define_env_flag(
    "PADDLE_TPU_MAX_REQUEST_BYTES", 1 << 28,
    "Per-request wire payload budget in bytes for serve/router frames "
    "(inference/serve.py); default 256 MiB.")
define_env_flag(
    "PADDLE_TPU_MEMBERSHIP_STORE", "",
    "Rendezvous-store endpoint for dynamic fleet membership "
    "(distributed/store/membership.py): 'host:port' dials a TCPStore, "
    "any other value is a FileStore directory. Backends publish TTL'd "
    "heartbeats there and a watching router add/removes them live. "
    "Empty disables membership.")
define_env_flag(
    "PADDLE_TPU_MEMBERSHIP_TTL", 5.0,
    "Seconds without heartbeat progress before a membership watcher "
    "declares a member dead (distributed/store/membership.py). A clean "
    "leave takes effect on the next poll regardless of TTL.")
define_env_flag(
    "PADDLE_TPU_MEMZ_OOM_DUMPS", 4,
    "OOM forensic dumps retained by the memory plane "
    "(observability/memz.py) and served at /memz?oom=1 — each captures "
    "the pool's per-owner rollups, fragmentation map, and allocation "
    "ring tail at the moment a PageExhausted fired.")
define_env_flag(
    "PADDLE_TPU_MEMZ_RING_CAPACITY", 4096,
    "Capacity of the memz allocation event ring "
    "(observability/memz.py): alloc/retain/release/exhausted events "
    "from every page pool, overwritten on wrap. 0 disables recording.")
define_env_flag(
    "PADDLE_TPU_METRICS_PORT", None,
    "Admin-plane port for /metrics,/healthz,/statusz,/varz,/alertz,"
    "/memz (inference/serve.py). 0 = ephemeral port; unset disables "
    "the admin server.", parser=int)
define_env_flag(
    "PADDLE_TPU_NO_X64", False,
    "Disable 64-bit jax types at import (core/dtype.py): int64/float64 "
    "tensors degrade to int32/float32.")
define_env_flag(
    "PADDLE_TPU_RETRACE", "warn",
    "Retrace-guard reaction when a compiled step's input signature changes "
    "mid-run (jit/compile_cache.py): 'warn', 'error' or 'off'.")
define_env_flag(
    "PADDLE_TPU_ROUTER_STREAM_RETRIES", 2,
    "Per-stream failover budget for router-proxied decode streams "
    "(inference/router.py): how many times one stream may be resumed on "
    "another backend after a mid-stream failure, on top of the shared "
    "RetryBudget. 0 fails streams on the first mid-stream fault.")
define_env_flag(
    "PADDLE_TPU_ROUTER_TENANT_MAX_INFLIGHT", 0,
    "Per-tenant in-flight request cap at the router (inference/"
    "router.py): a tenant at the cap is shed with RESOURCE_EXHAUSTED "
    "(outcome 'tenant_shed') before touching any backend or the shared "
    "retry budget. 0 disables the cap.")
define_env_flag(
    "PADDLE_TPU_SERVE_BATCH", 0,
    "Default max dynamic-batch size for serve when --batch is not given "
    "(inference/serve.py); 0/1 serializes requests.")
define_env_flag(
    "PADDLE_TPU_SERVE_BUCKETS", "",
    "Padded-batch bucket ladder override for the dynamic batcher, "
    "space/comma-separated positive ints (inference/batching.py); empty "
    "uses powers of two up to max batch.")
define_env_flag(
    "PADDLE_TPU_SERVE_IDLE_TIMEOUT", 600.0,
    "Seconds an idle client connection is kept open before the server "
    "closes it (inference/serve.py).")
define_env_flag(
    "PADDLE_TPU_SERVE_MAX_QUEUE", 0,
    "Admission-control watermark: queued requests past this are shed with "
    "RESOURCE_EXHAUSTED (inference/batching.py). 0 disables shedding.")
define_env_flag(
    "PADDLE_TPU_SERVE_REQUEST_TIMEOUT", 120.0,
    "Server-side per-request deadline in seconds (inference/serve.py).")
define_env_flag(
    "PADDLE_TPU_SERVE_ROLE", "unified",
    "Serving-topology role this worker advertises (inference/serve.py "
    "--role): 'unified' (prefill + decode, the default), 'prefill' "
    "(prompt forward + KV export only) or 'decode' (KV import + token "
    "streaming). Non-unified roles require decode mode, imply "
    "PADDLE_TPU_DECODE_HANDOFF, and are published in the membership "
    "meta so a topology-aware router can route prefill->handoff->decode "
    "(docs/serving.md).")
define_env_flag(
    "PADDLE_TPU_SERVE_TRAILING", "auto",
    "Trailing-dim padding policy for the dynamic batcher: 'auto' (probe), "
    "'on' or 'off' (inference/batching.py).")
define_env_flag(
    "PADDLE_TPU_SLO_AVAILABILITY", 0.999,
    "Availability SLO target fraction for burn-rate alerts "
    "(observability/slo.py).")
define_env_flag(
    "PADDLE_TPU_SLO_BURN", "2,10",
    "Burn-rate alert factors 'warn,firing' (observability/slo.py).")
define_env_flag(
    "PADDLE_TPU_SLO_LATENCY_TARGET", 0.99,
    "Fraction of requests that must beat PADDLE_TPU_SLO_P99_MS "
    "(observability/slo.py).")
define_env_flag(
    "PADDLE_TPU_SLO_P99_MS", 0.0,
    "Latency SLO threshold in milliseconds; 0 disables the latency "
    "objective (observability/slo.py).")
define_env_flag(
    "PADDLE_TPU_SLO_TENANTS", "",
    "Comma-separated tenant names that get per-tenant availability "
    "objectives on /alertz (observability/slo.py), e.g. 'batch,search'. "
    "Per-tenant sheds are excluded from the fleet-wide availability "
    "objective either way; empty registers no per-tenant objectives.")
define_env_flag(
    "PADDLE_TPU_SLO_WINDOWS", "60,300",
    "Burn-rate evaluation windows 'short,long' in seconds "
    "(observability/slo.py).")
define_env_flag(
    "PADDLE_TPU_STALL_DUMP", "",
    "Directory for flight-recorder stall dumps (observability/"
    "flight_recorder.py); empty disables the watchdog.")
define_env_flag(
    "PADDLE_TPU_STALL_TIMEOUT", 60.0,
    "Seconds without a heartbeat before the flight recorder declares a "
    "stall and dumps thread stacks (observability/flight_recorder.py).")
define_env_flag(
    "PADDLE_TPU_STORE_RETRIES", 3,
    "TCPStore request retry budget (distributed/store).")
define_env_flag(
    "PADDLE_TPU_TENANT_QUOTA", "",
    "Per-tenant service-rate quotas, 'tenant:rate,...' with '*' for "
    "unlisted tenants: tokens/second in the decode engine, rows/second "
    "in the dynamic batcher (inference/batching.py tenant_quotas). A "
    "tenant in quota debt is deferred — queued, never dropped. Empty or "
    "0 = unlimited.")
define_env_flag(
    "PADDLE_TPU_TENANT_WEIGHTS", "",
    "Per-tenant fair-share weights, 'tenant:weight,...' with '*' for "
    "unlisted tenants (inference/batching.py tenant_weights). Admission "
    "picks the most-underserved tenant by tokens_served/weight; unlisted "
    "tenants weigh 1.0.")
define_env_flag(
    "PADDLE_TPU_TRACEZ_CAPACITY", 1048576,
    "Event capacity of the per-process tracez ring (observability/"
    "tracez.py); oldest events are overwritten when full. 0 disables "
    "event recording entirely.")
define_env_flag(
    "PADDLE_TPU_TRACE_FILE", "",
    "JSONL sink for sampled request traces (observability/spans.py); "
    "empty disables the file sink.")
define_env_flag(
    "PADDLE_TPU_TRACE_MAX_BYTES", 64 << 20,
    "Size cap in bytes for the JSONL trace file (observability/spans.py): "
    "at the cap the file rotates to <path>.1, keeping the live file plus "
    "one predecessor. <= 0 disables rotation.")
define_env_flag(
    "PADDLE_TPU_TRACE_SAMPLE", 0.0,
    "Request-trace sampling rate clamped to [0,1] (observability/spans.py); "
    "0 disables sampling.")
define_env_flag(
    "PADDLE_TPU_TSAN", False,
    "Arm the tsan-lite runtime concurrency sanitizer "
    "(analysis/runtime/): threading.Lock/RLock/Condition/Thread are "
    "replaced with instrumented shims that detect lock-order inversions "
    "(TPR101), blocking-under-lock (TPR102) and leaked threads/locks "
    "(TPR103). Off = nothing is patched.")
define_env_flag(
    "PADDLE_TPU_TSAN_BLOCK_MS", 150.0,
    "TPR102 threshold in milliseconds: a lock hold segment at or past "
    "this wall-clock duration is reported as blocking work under a lock "
    "(analysis/runtime/sanitizer.py). Condition.wait on the held lock "
    "suspends the segment.")
define_env_flag(
    "PADDLE_TPU_TSAN_REPORT", "",
    "Path where the tsan-lite pytest plugin writes its JSON findings "
    "report (analysis/runtime/pytest_plugin.py); replay it with "
    "`python -m paddle_tpu.analysis --runtime <report>`. Empty skips "
    "the file.")
define_env_flag(
    "PADDLE_TPU_VARZ_CAPACITY", 400,
    "Ring-buffer capacity (snapshot count) for /varz time-series history "
    "(observability/timeseries.py), min 8.")
define_env_flag(
    "PADDLE_TPU_VARZ_INTERVAL", 10.0,
    "Sampler period in seconds for /varz time-series history "
    "(observability/timeseries.py), min 0.05.")


if __name__ == "__main__":
    # `python -m paddle_tpu.core.flags > docs/flags.md` regenerates the doc.
    print(env_flags_markdown(), end="")
