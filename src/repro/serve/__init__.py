"""Continuous-batching serving (`repro.serve`).

The serving counterpart of the zero-stall kernels: decode is
bandwidth-bound and batch-starved (TROOP's low-operational-intensity
analysis; "Know your rooflines!", PAPERS.md), so the way to serve
heavy traffic fast is to keep the decode batch full — admit new
requests into freed slots every step (continuous batching), ingest
prompts in ONE fused ``Model.prefill`` call instead of ``prompt_len``
lock-step dispatches, and amortize per-token host control across
``steps_per_dispatch`` fused decode+sample iterations (on-device
sampling + one sync per block — the serving analogue of the paper's
zero-overhead loop nests).

    from repro.serve import ServeEngine, Request

    engine = ServeEngine(model, params, ctx, num_slots=8, max_len=256,
                         steps_per_dispatch=4)
    results = engine.run([Request(rid=i, prompt=p, max_new_tokens=32,
                                  temperature=0.8, top_p=0.95, seed=i)
                          for i, p in enumerate(prompts)])

Pieces:

* :mod:`repro.serve.engine`   — `ServeEngine` (slots, admission, block
  decode dispatch, streaming) and the `lockstep_generate` correctness
  oracle.
* :mod:`repro.serve.stats`    — typed `EngineStats` (aggregates,
  per-request TTFT/queue-wait and per-token latency samples, derived
  throughput, `snapshot()`); `engine.stats` is one of these.
* :mod:`repro.serve.sampling` — on-device batched greedy/temperature/
  top-k/top-p sampling over per-slot PRNG key rows.
* :mod:`repro.serve.request`  — `Request` / `GenerationResult` types.
* :mod:`repro.serve.cluster`  — the fleet tier: `ShardedEngine`
  (model-parallel decode over a device mesh) and `Router` (N
  data-parallel replicas, load-aware admission, fault-tolerant
  re-queue with at-most-once token emission).

Observability: the engine emits `serve.admit` / `serve.dispatch` spans
and `serve.retire` events through :mod:`repro.obs` when tracing is
enabled (near-zero cost otherwise), and `engine.run` accepts separate
`prefill_timeout_s` / `decode_timeout_s` budgets.

Variable-length correctness rides the masked flash-attention path
(:func:`repro.kernels.ops.attention` with per-sequence lengths), so
ragged continuous batches stay on the Pallas kernel.
"""

from repro.serve import sampling
from repro.serve.cluster import Router, ShardedEngine
from repro.serve.engine import ServeEngine, lockstep_generate, paged_view
from repro.serve.request import GenerationResult, Request
from repro.serve.stats import EngineStats

__all__ = ["ServeEngine", "EngineStats", "Request", "GenerationResult",
           "Router", "ShardedEngine", "lockstep_generate", "paged_view",
           "sampling"]
