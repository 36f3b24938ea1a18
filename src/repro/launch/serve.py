"""Serving driver: batched prefill + decode with continuous batching hooks.

Demonstrates the inference side of the framework end-to-end on local
devices: prefill a batch of prompts, then decode tokens with the sharded
KV/SSM caches, with per-token latency stats and HBM energy estimates from
the paper's power model.

Params and caches are sharded under a ``make_local_mesh(data, model)``
mesh via the same sharding-rule machinery the dry-run cells use, so the
smoke path exercises the production layout (trivially, on one device).

``--power-report`` turns on the power side: the compiled decode step's
HBM traffic (execution-count-weighted HLO analysis, as in the dry run) is
apportioned per sequence, wrapped into DRAM command traces carrying the
decode batch's actual output bytes, and scored through the estimation
service (``repro.serving``): lint-gated admission, ring-bucketed pad
shapes (bounded jit cache across ``--batch`` sizes), the model
device-resident, one batched dispatch per window — plus the
HBM2e-anchored extrapolation (``repro.core.hbm``).  The scorer is any
unified-protocol estimator (``repro.core.model_api``): ``--power-model
vampire|micron|drampower`` picks the physics, ``--power-impl
vectorized|pallas|reference`` picks the impl-registry evaluation path
(``pallas`` = the fused (traces x vendors) kernel family), and
``--vampire PATH`` loads a saved model (v2 ``.npz`` or legacy v1 pickle)
instead of the quick reference fit.

    python -m repro.launch.serve --arch qwen2.5-3b --smoke --batch 4 \
        --prompt-len 64 --decode-tokens 32 --data 1 --model 1 \
        --temperature 0.7 --power-report --power-model vampire

``--no-smoke`` selects the architecture's published widths (random
weights from ``--seed``; nothing is downloaded):

    python -m repro.launch.serve --arch qwen2.5-3b --no-smoke \
        --decode-tokens 8 --power-report --power-impl pallas
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs import registry
from repro.launch import hlo_analysis
from repro.launch.mesh import make_local_mesh
from repro.launch.steps import shard_tree
from repro.models.lm import LM
from repro.models.meta import specs_for
from repro.sharding import rules as R


@dataclasses.dataclass
class ServeJob:
    arch: str
    smoke: bool = True
    batch: int = 4
    prompt_len: int = 64
    decode_tokens: int = 32
    data: int = 1
    model: int = 1
    seed: int = 0
    temperature: float = 0.0
    # power reporting (off by default: it fits/loads a VAMPIRE model)
    power_report: bool = False
    power_vendors: tuple[int, ...] = (0, 1, 2)
    power_model: str = "vampire"      # estimator kind: vampire|micron|drampower
    power_impl: str = "vectorized"    # impl registry: vectorized|pallas|reference
    vampire_path: str | None = None   # saved model blob (model_api v2 / v1)


def run(job: ServeJob) -> dict:
    cfg = registry.get_config(job.arch, smoke=job.smoke)
    lm = LM(cfg)
    mesh = make_local_mesh(data=job.data, model=job.model)
    max_len = job.prompt_len + job.decode_tokens
    plan = R.plan_for(cfg, "decode", job.batch, mesh, False, seq_len=max_len)

    # ---- params sharded under the mesh by the cell sharding rules --------
    params = lm.init(jax.random.key(job.seed))
    pshard = shard_tree(mesh, specs_for(lm.param_meta(), plan.rules, mesh))
    params = jax.device_put(params, pshard)

    n_data = mesh.shape.get("data", 1)
    bentry = "data" if job.batch % n_data == 0 else None
    rng = np.random.default_rng(job.seed)
    prompts = jax.device_put(
        jnp.asarray(rng.integers(0, cfg.vocab,
                                 size=(job.batch, job.prompt_len)),
                    dtype=jnp.int32),
        NamedSharding(mesh, P(bentry, None)))
    aux = None
    if cfg.aux_seq:
        aux = jnp.zeros((job.batch, cfg.aux_seq, cfg.d_model),
                        jnp.dtype(cfg.dtype))

    # ---- prefill: emit the decode-layout (mesh-sharded) caches -----------
    cshard = shard_tree(
        mesh, specs_for(lm.init_cache_meta(job.batch, max_len),
                        plan.rules, mesh))
    logits_shard = NamedSharding(mesh, P(bentry, "model"))
    t0 = time.perf_counter()
    prefill = jax.jit(lambda p, t: lm.prefill(p, t, aux=aux,
                                              max_len=max_len),
                      out_shardings=(logits_shard, cshard))
    logits, caches = prefill(params, prompts)
    logits.block_until_ready()
    t_prefill = time.perf_counter() - t0

    tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
    # one AOT compile: the decode loop and the power report's HLO traffic
    # analysis share the same compiled executable
    decode = jax.jit(lm.decode_step, donate_argnums=(1,),
                     out_shardings=(logits_shard, cshard)
                     ).lower(params, caches, tok).compile()
    generated = [tok]
    lat = []
    for i in range(job.decode_tokens - 1):
        t1 = time.perf_counter()
        logits, caches = decode(params, caches, tok)
        logits.block_until_ready()
        lat.append(time.perf_counter() - t1)
        if job.temperature > 0:
            key = jax.random.fold_in(jax.random.key(job.seed + 1), i)
            tok = jax.random.categorical(
                key, logits / job.temperature, axis=-1).astype(jnp.int32)[:, None]
        else:
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
        generated.append(tok)

    tokens = jnp.concatenate(generated, axis=1)
    lat = np.asarray(lat[1:]) if len(lat) > 1 else np.asarray(lat)
    res = {
        "tokens": np.asarray(tokens),
        "prefill_s": t_prefill,
        "decode_p50_ms": float(np.median(lat) * 1e3) if lat.size else 0.0,
        "decode_p99_ms": float(np.percentile(lat, 99) * 1e3)
        if lat.size else 0.0,
        "tokens_per_s": (job.batch * lat.size / lat.sum())
        if lat.size and lat.sum() > 0 else 0.0,
    }
    if job.power_report:
        res["power"] = power_report(job, decode, logits, tokens,
                                    n_data=n_data,
                                    step_seconds=float(np.median(lat))
                                    if lat.size else 1e-3,
                                    mesh=mesh)
    return res


# ---------------------------------------------------------------------------
# Power reporting (the "HBM energy estimates" half of the module contract)
# ---------------------------------------------------------------------------
def _decode_traffic_bytes(compiled) -> float:
    """Per-step, per-device HBM traffic of the compiled decode step
    (execution-count-weighted HLO analysis; falls back to XLA's own
    'bytes accessed' when the text analysis finds nothing)."""
    rep = hlo_analysis.analyze_hlo(compiled.as_text())
    if rep.traffic_bytes > 0:
        return float(rep.traffic_bytes)
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    return float(ca.get("bytes accessed", 0.0)) if ca else 0.0


def _load_estimator(job: ServeJob):
    """Resolve the power model: a saved blob if given (any kind the v2
    loader knows), else the quick reference fit — then adapt it to the
    requested ``--power-model`` kind through the protocol registry."""
    from repro.core import model_api
    from repro.core.vampire import reference_vampire
    if job.vampire_path:
        model = model_api.load_estimator(job.vampire_path)
        if model.kind == job.power_model:
            return model
        if model.kind != "vampire":
            raise ValueError(
                f"{job.vampire_path} holds a {model.kind!r} estimator but "
                f"--power-model={job.power_model!r} was requested")
    else:
        model = reference_vampire()
    return model_api.make_estimator(job.power_model, model)


def lint_ingested(seq_traces) -> None:
    """Batched protocol lint of traces bound for the power report.
    Raises :class:`repro.analysis.TraceProtocolError` carrying the
    structured diagnostics (rule id, trace + command index, bank) when any
    ingested trace is protocol-illegal — a corrupt external trace must be
    rejected, not silently priced.

    ``power_report`` itself now admits through the
    :class:`~repro.serving.EstimationService` (whose gate runs the same
    linter and raises with the same origin); this standalone hook remains
    for callers linting traces without standing up a service."""
    from repro.analysis import trace_lint
    trace_lint.lint_ingested(seq_traces, origin="serve.power_report")


def power_report(job: ServeJob, compiled_decode, logits, tokens, *,
                 n_data: int, step_seconds: float, mesh=None) -> dict:
    """Score one decode batch's HBM traffic through the estimation service.

    One DRAM command trace per sequence (carrying that sequence's actual
    logits/token bytes as line data), admitted through the
    :class:`~repro.serving.EstimationService` — lint-gated ingestion, the
    ring's bucketed pad shapes (so varying ``--batch`` sizes stop growing
    the jit cache: windows land on a small fixed shape vocabulary), the
    model kept device-resident, and the dispatch sharded over ``mesh``
    when it has more than one device.  Energies scale from each trace's
    modeled bytes to the step's measured traffic share; the service's
    metrics snapshot rides along under ``"serving"``."""
    from repro.core import hbm, traces
    from repro.core.dram import LINE_BYTES

    model = _load_estimator(job)
    vendors = [v for v in job.power_vendors if v in model.vendors]
    traffic = _decode_traffic_bytes(compiled_decode)
    # the HLO traffic is per DEVICE; with the batch sharded over the data
    # axis each device's step only covers batch/n_data sequences
    local_batch = (job.batch // n_data if job.batch % n_data == 0
                   else job.batch)
    bytes_per_seq = traffic / max(local_batch, 1)

    logits_np = np.asarray(logits, np.float32)
    tokens_np = np.asarray(tokens)
    seq_traces = []
    for b in range(job.batch):
        # the sequence's real decode output bytes, recycled to fill the
        # traffic share (decode re-reads the same weights every step, so
        # repeating content is the honest analogue)
        payload = logits_np[b].tobytes() + tokens_np[b].tobytes()
        lines = traces.lines_from_bytes(payload)
        n_req = int(min(max(bytes_per_seq // LINE_BYTES, 8), 512))
        reps = int(np.ceil(n_req / max(len(lines), 1)))
        lines = np.tile(lines, (max(reps, 1), 1))[:n_req]
        spec = traces.AppSpec(f"decode{b}", intensity=0.8, row_hit=0.7,
                              read_frac=0.85, data_dist="random",
                              seed=job.seed + b)
        seq_traces.append(traces.app_trace(spec, n_requests=n_req,
                                           lines=lines))

    # ingestion + scoring through the serving stack: the service lints on
    # admission (never bill a protocol-illegal trace) and dispatches the
    # whole batch on the ring's bucketed pad shapes
    from repro.analysis import trace_lint
    from repro.serving import EstimationService, ServiceConfig
    svc = EstimationService(model, ServiceConfig(impl=job.power_impl),
                            mesh=mesh)
    tickets, rejections = svc.submit_many(seq_traces, vendors)
    if rejections:
        raise trace_lint.TraceProtocolError(
            [d for r in rejections for d in r.diagnostics],
            origin="serve.power_report")
    svc.close()
    rows = [svc.result(t) for t in tickets]               # B vendor-rows

    modeled_bytes = np.asarray(
        [traces.trace_request_lines(tr).shape[0] * LINE_BYTES
         for tr in seq_traces], np.float64)
    scale = (bytes_per_seq / np.maximum(modeled_bytes, 1.0))[:, None]
    energy_pj = np.asarray([r.energy_pj for r in rows],
                           np.float64) * scale            # (B, V) per step

    out = {
        "vendors": list(vendors),
        "power_model": model.kind,
        "traffic_bytes_per_step": traffic,
        "bytes_per_seq_per_step": bytes_per_seq,
        "ddr_energy_pj_per_seq_step": energy_pj,          # (B, V)
        "ddr_energy_uj_per_token_mean": float(energy_pj.mean() * 1e-6),
        "serving": dataclasses.asdict(svc.metrics()),
    }
    # the HBM2e-anchored extrapolation needs fitted VAMPIRE PowerParams;
    # the datasheet baselines have none (no data dependency to anchor)
    if model.kind == "vampire":
        ones_frac, toggle_frac = hbm.tensor_stats(logits)
        hmodel = hbm.HbmEnergyModel.from_vampire(model.params(vendors[0]))
        step = hbm.step_energy(hmodel, read_bytes=traffic * 0.85,
                               write_bytes=traffic * 0.15,
                               step_seconds=step_seconds,
                               ones_frac=ones_frac, toggle_frac=toggle_frac)
        out.update(hbm_step_energy_uj=step.total_pj * 1e-6,
                   hbm_ones_frac=ones_frac, hbm_toggle_frac=toggle_frac)
    return out


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default="qwen2.5-3b")
    p.add_argument("--smoke", action=argparse.BooleanOptionalAction,
                   default=True,
                   help="reduced smoke widths (default); --no-smoke runs "
                        "the published widths")
    p.add_argument("--batch", type=int, default=4)
    p.add_argument("--prompt-len", type=int, default=64)
    p.add_argument("--decode-tokens", type=int, default=32)
    p.add_argument("--data", type=int, default=1,
                   help="data-parallel mesh axis size")
    p.add_argument("--model", type=int, default=1,
                   help="model-parallel mesh axis size")
    p.add_argument("--temperature", type=float, default=0.0)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--power-report", action="store_true")
    p.add_argument("--power-model", default="vampire",
                   choices=("vampire", "micron", "drampower"),
                   help="estimator kind scoring the decode HBM traffic")
    from repro.core import model_api
    p.add_argument("--power-impl", default="vectorized",
                   choices=model_api.registered_impls(),
                   help="impl-registry evaluation path for the power "
                        "report (pallas = fused kernels; compiled on TPU, "
                        "interpret elsewhere)")
    p.add_argument("--vampire", default=None,
                   help="saved model blob (model.save: v2 .npz, or legacy "
                        "v1 pickle); quick reference fit when omitted")
    args = p.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    res = run(ServeJob(arch=args.arch, smoke=args.smoke, batch=args.batch,
                       prompt_len=args.prompt_len,
                       decode_tokens=args.decode_tokens,
                       data=args.data, model=args.model, seed=args.seed,
                       temperature=args.temperature,
                       power_report=args.power_report,
                       power_model=args.power_model,
                       power_impl=args.power_impl,
                       vampire_path=args.vampire))
    print(f"prefill={res['prefill_s']:.2f}s decode p50={res['decode_p50_ms']:.1f}ms "
          f"p99={res['decode_p99_ms']:.1f}ms throughput={res['tokens_per_s']:.1f} tok/s")
    if "power" in res:
        pw = res["power"]
        line = (f"power[{pw['power_model']}]: "
                f"{pw['traffic_bytes_per_step']/1e6:.1f} MB/step HBM "
                f"traffic, DDR-model {pw['ddr_energy_uj_per_token_mean']:.2f} "
                f"uJ/token (vendors {pw['vendors']})")
        if "hbm_step_energy_uj" in pw:
            line += f", HBM2e-anchored {pw['hbm_step_energy_uj']:.1f} uJ/step"
        print(line)


if __name__ == "__main__":
    main()
