"""Profiling & speed telemetry.

Reference (SURVEY.md §5): Composer's Profiler with cyclic schedule + JSON
trace handler, llm-foundry ``speed_monitor``/``runtime_estimator`` callbacks,
and photon's manual ``time.time_ns()`` spans. TPU equivalents:

- the name registry: every KPI, span, event and ``jax.named_scope`` name the
  program writes, as module constants (spans are ``telemetry.span``, traces
  ``telemetry.ProfileController``);
- :func:`model_flops_per_token` / :class:`SpeedMonitor` — tokens/sec and MFU
  against a configurable peak (defaults to TPU v5e bf16 peak).
"""

from __future__ import annotations

import dataclasses

from photon_tpu.config.schema import ModelConfig


# ---------------------------------------------------------------------------
# KPI name registry (ISSUE 4 satellite): every ``server/*`` / ``client/*``
# metric name the runtime records into History is declared HERE as a module
# constant — record sites import the constant, a registry test
# (tests/test_telemetry.py) asserts no stringly-typed name drifts past this
# file, and the tracing plane reuses the same constants as span names so
# KPIs and spans agree on vocabulary.
# ---------------------------------------------------------------------------

# -- server round-loop phases (federation/server.py) ----------------------
ROUND_TIME = "server/round_time"
FIT_ROUND_TIME = "server/fit_round_time"
BROADCAST_PRE_TIME = "server/broadcast_pre_time"
BROADCAST_POST_TIME = "server/broadcast_post_time"
CHECKPOINT_TIME = "server/checkpoint_time"
CKPT_BARRIER_WAIT_S = "server/ckpt_barrier_wait_s"
STEPS_CUMULATIVE = "server/steps_cumulative"
ROUND_FAILED = "server/round_failed"
EVAL_ROUND_FAILED = "server/eval_round_failed"
# span-only phase names (no KPI twin: the KPI would duplicate round_time
# decomposition already carried by the spans)
SAMPLE_CLIENTS_SPAN = "server/sample_clients"
EVAL_ROUND_SPAN = "server/eval_round"
#: Strategy.apply_average: pseudo-gradient, server rule, norms
SERVER_UPDATE_SPAN = "server/update"
# whole-unit umbrella spans: deliberately NOT the KPI names — the KPI
# server/round_time is measured from fit_round entry (excludes broadcast/
# eval/checkpoint) and client/fit_time is the train loop alone, while these
# spans cover the full round / full fit. A span may share a KPI's name ONLY
# when it measures the same window.
ROUND_SPAN = "server/round"
CLIENT_FIT_SPAN = "client/fit"

# -- server aggregation / strategy (strategy/base.py, metrics.py) ---------
N_CLIENTS = "server/n_clients"
N_SAMPLES = "server/n_samples"
EFFECTIVE_LR = "server/effective_lr"
EVAL_LOSS = "server/eval_loss"
EVAL_SAMPLES = "server/eval_samples"
PSEUDO_GRAD_NORM = "server/pseudo_grad_norm"
PARAM_NORM = "server/param_norm"
GNS_TRACE_EST = "server/gns_trace_est"
GNS_SQNORM_EST = "server/gns_sqnorm_est"
GRADIENT_NOISE_SCALE = "server/gradient_noise_scale"
COLLECTIVE_AGG_TIME = "server/collective_agg_time"

# -- device-resident aggregation plane (parallel/collective_agg.py) -------
# Hierarchy stage decomposition of COLLECTIVE_AGG_TIME (which spans all
# three), recorded per round by CollectiveFedRunner:
#: host rows → client-axis-sharded device arrays (stack + device_put)
COLLECTIVE_STACK_TIME = "server/collective_stack_time"
#: the fused SPMD program: hierarchical reduce (+ q8 codec) + server update
COLLECTIVE_EXCHANGE_TIME = "server/collective_exchange_time"
#: replicated result → host (broadcast/checkpoint mirror fetch; on the
#: host-optimizer path also the host strategy update itself)
COLLECTIVE_UPDATE_TIME = "server/collective_update_time"
#: modeled cross-slice DCN bytes this round (idealized once-across model,
#: ``collective_agg.modeled_cross_slice_bytes`` — the fp32-vs-q8 ratio is
#: the number that matters, not the absolute)
COLLECTIVE_WIRE_BYTES = "server/collective_wire_bytes"

# -- elastic collective rounds (ISSUE 8, federation/collective_round.py) --
#: clients missing from this round's surviving cohort (failed fits +
#: liveness-excluded); 0 every round on a fault-free run
COLLECTIVE_STRAGGLERS = "server/collective_stragglers"
#: 1.0 when this round degraded to the host-plane ``aggregate_inplace``
#: fold (below quorum / retry budget exhausted), else 0.0 — the runner
#: keeps the cumulative count on ``degraded_rounds_total``
COLLECTIVE_DEGRADED_ROUNDS = "server/collective_degraded_rounds"
#: seconds spent reconfiguring the gang this round (survivor-cohort mesh
#: rebuild + re-run attempts after a missed stage deadline); 0.0 when the
#: first attempt lands
COLLECTIVE_RECONFIG_TIME = "server/collective_reconfig_time"

# -- ZeRO-1 sharded server update + layout auto-tuner (ISSUE 14) ----------
#: per-rank fraction of the full server state (params + optimizer moments)
#: resident on the device plane: 1.0 replicated, ≈1/replica on the ZeRO-1
#: sharded plane (chunk padding makes it marginally larger)
OPT_SHARD_FRAC = "server/opt_shard_frac"
#: wall seconds of the post-update params ICI all-gather + host fetch (the
#: ONE all-gather of a sharded round — it runs after the update, inside
#: the update leg; 0.0 on the replicated plane, where params never shard)
OPT_ALLGATHER_TIME = "server/opt_allgather_time"
#: wall seconds the layout auto-tuner (parallel/autotune.py) spent
#: enumerating + ranking (data, fsdp, tensor, pipe) meshes for this
#: client's device slice
LAYOUT_SEARCH_TIME = "server/layout_search_time"
#: the auto-tuner's analytic step-time estimate for the layout it picked
#: (compare against the measured step time to audit the cost model)
LAYOUT_EST_STEP_S = "server/layout_est_step_s"

# -- asynchronous federated rounds (ISSUE 18, federation/async_round.py) --
# Version-clock KPIs recorded by AsyncFedRunner into History at each
# version advance (the async analog of the per-round KPI block above):
#: the server version after this advance (the monotone version clock)
ASYNC_VERSION = "server/async_version"
#: client deltas folded into this advance (== the K buffer unless a
#: same-instant burst advanced multiple versions at once)
ASYNC_ARRIVALS = "server/async_arrivals"
#: mean / max staleness (server_version − client_base_version) across the
#: deltas folded this advance; 0 everywhere == the synchronous round
ASYNC_STALENESS_MEAN = "server/async_staleness_mean"
ASYNC_STALENESS_MAX = "server/async_staleness_max"
#: mean staleness-discount weight multiplier applied this advance (1.0 at
#: zero staleness — the bit-parity regime)
ASYNC_DISCOUNT_MEAN = "server/async_discount_mean"
#: cumulative deltas rejected for staleness > max_staleness (each gets a
#: fresh-version re-broadcast, never an aborted run)
ASYNC_REJECTED = "server/async_rejected_total"
#: cumulative in-flight deltas dropped on a LivenessTracker dead edge
ASYNC_DROPPED = "server/async_dropped_total"
#: cumulative buffer-full moments where < min_arrivals distinct clients
#: had landed — the version clock held still (stall, not abort)
ASYNC_STALLS = "server/async_stalls_total"
#: buffered deltas awaiting the next advance, sampled after each arrival
ASYNC_BUFFER_FILL = "server/async_buffer_fill"
#: simulated seconds elapsed when this version committed — the modeled
#: clock time-to-target-loss is compared on (``tests/test_async_round.py``)
ASYNC_SIM_TIME = "server/async_sim_time"
#: the chaos fit_delay_plan slowdown factor this fit ran under (1.0 =
#: no injected skew; the async runner scales simulated durations by it)
CLIENT_FIT_DELAY_FACTOR = "client/fit_delay_factor"

# -- wire / compression plane (WireStats.metrics_since) -------------------
WIRE_UPLINK_RAW_BYTES = "server/wire_uplink_raw_bytes"
WIRE_UPLINK_BYTES = "server/wire_uplink_bytes"
WIRE_BROADCAST_BYTES = "server/wire_broadcast_bytes"
WIRE_COMPRESSION_RATIO = "server/wire_compression_ratio"

# -- client-side KPIs (train/trainer.py, federation/client_runtime.py) ----
CLIENT_FIT_TIME = "client/fit_time"
CLIENT_FIT_INIT_TIME = "client/fit_init_time"
CLIENT_FIT_SET_PARAMETERS_TIME = "client/fit_set_parameters_time"
CLIENT_STEPS = "client/steps"
CLIENT_TOKENS_PER_SEC = "client/tokens_per_sec"
CLIENT_FINAL_LOSS = "client/final_loss"
CLIENT_LR = "client/lr"
CLIENT_PSEUDO_GRAD_NORM = "client/pseudo_grad_norm"
CLIENT_PARAM_NORM = "client/param_norm"
CLIENT_SKIPPED_ROUND = "client/skipped_round"
# span-only client phases (telemetry plane)
CLIENT_RESOLVE_PARAMS_SPAN = "client/resolve_params"
CLIENT_TRAIN_SPAN = "client/train"
CLIENT_ENCODE_SPAN = "client/encode"
CLIENT_PACKAGE_SPAN = "client/package"
CLIENT_EVALUATE_SPAN = "client/evaluate"
#: the pseudo-gradient difference and its two L2 norms, on the host
CLIENT_PSEUDO_GRAD_NORM_SPAN = "client/pseudo_grad_norm_time"
#: a node caching a round's broadcast (federation/node.py)
NODE_SET_BROADCAST_SPAN = "node/set_broadcast"
# -- trainer span names (train/trainer.py; spans only) --------------------
#: flat host arrays -> the sharded device state, and back
TRAINER_SET_PARAMETERS_SPAN = "trainer/set_parameters"
TRAINER_GET_PARAMETERS_SPAN = "trainer/get_parameters"
#: inside Trainer.fit: the dispatch loop, each wait on the prefetcher, and
#: the closing block_until_ready
TRAINER_STEPS_SPAN = "trainer/steps"
TRAINER_NEXT_BATCH_SPAN = "trainer/next_batch"
TRAINER_FENCE_SPAN = "trainer/fence"
#: opened and closed inside the fence once the last step's metrics are on the
#: host, only when the step has dropless expert layers: attrs ``rows_held``,
#: ``max_expert_load``, ``dispatch_rows_moved`` and ``dispatch_rows_static``
#: (the four counters below), for a trace's reader
TRAINER_MOE_LOAD_SPAN = "trainer/moe_load"
# -- dropless expert layers (ops/moe.py): counters in the train step's
# metrics, fetched with the loss at the trainer's fence -------------------
#: assignments routed to the experts held here, summed over the layers
MOE_ROWS_HELD = "moe/rows_held"
#: the busiest held expert's rows over the held experts' mean, worst layer
MOE_MAX_EXPERT_LOAD = "moe/max_expert_load"
#: rows the dispatch's six row movements a layer copied (the blocks they
#: visited), and their static worst case (6 ``N k`` a layer), summed over the
#: layers: their ratio is how far the dispatch followed the rows held
MOE_DISPATCH_ROWS_MOVED = "moe/dispatch_rows_moved"
MOE_DISPATCH_ROWS_STATIC = "moe/dispatch_rows_static"
#: opened and closed inside the fence like ``trainer/moe_load``, only when the
#: step holds the indexer's sparse attention: attrs ``picked_pairs``,
#: ``causal_pairs``, ``tiles_visited``, ``tiles_causal``, ``index_loss`` (the
#: five counters below, of the fit's last step) and the static
#: ``index_loss_kernel``, ``index_loss_tiles``, ``index_loss_tiles_skipped``
#: (which path makes the index loss's ``pbar``, and its launches' key tiles),
#: ``select_kernel``, ``select_launches`` (which path searches the
#: selection's thresholds, and its launches a step: one a query chunk)
TRAINER_DSA_SPAN = "trainer/dsa"
# -- learned sparse attention (models/mpt.py, ops/dsa.py): counters in the
# train step's metrics, summed over the layers, fetched with the loss -------
#: (query, key) pairs the indexers' selections picked
DSA_PICKED_PAIRS = "dsa/picked_pairs"
#: (query, key) pairs with the key no later than the query
DSA_CAUSAL_PAIRS = "dsa/causal_pairs"
#: tiles of the masked kernel's forward launch that hold a picked pair (a
#: tile is one for all heads), and those that hold a causal pair
DSA_TILES_VISITED = "dsa/tiles_visited"
DSA_TILES_CAUSAL = "dsa/tiles_causal"
#: the layers' index losses, summed (what the step adds to the cross-entropy)
DSA_INDEX_LOSS = "dsa/loss"
# -- its ``jax.named_scope``s, in every operation's ``op_name`` ------------
#: the indexer's three projections, its key norm and the rotation
DSA_INDEXER_SCOPE = "dsa/indexer"
#: the index scores by query chunk, each query's threshold (a launch a chunk
#: under its own ``index_select`` where the attention runs its kernel), the
#: mask and its tile counts
DSA_SELECT_SCOPE = "dsa/select"
#: the second pass over q.k for the heads' mean probabilities (a launch a
#: chunk under its own ``index_pbar`` where the attention runs its kernel),
#: the index scores again, the loss and its gradient into the indexer
DSA_INDEX_LOSS_SCOPE = "dsa/index_loss"
#: the per-head RMSNorm of q and k before the rotation
ATTN_QK_NORM_SCOPE = "attn/qk_norm"
# -- Mamba-2 layers (models/mpt.py, ops/ssd.py): ``jax.named_scope``s in
# every operation's ``op_name``, forward, transpose and recomputation alike --
#: the mixer's in- and out-projection
MAMBA_PROJ_SCOPE = "mamba/proj"
#: the causal depthwise convolution and its SiLU
MAMBA_CONV_SCOPE = "mamba/conv"
#: dt's softplus, the chunked scan (``ops/ssd.ssd_scan``) and the skip term
MAMBA_SCAN_SCOPE = "mamba/scan"
#: the gate ``y * silu(z)`` and the RMSNorm over all inner channels
MAMBA_GATE_NORM_SCOPE = "mamba/gate_norm"
# -- gated short-convolution layers (models/mpt.py over ops/ssd.causal_conv1d):
# ``jax.named_scope``s like the Mamba-2 layers'; no pattern over one family's
# names matches the other's --------------------------------------------------
#: the mixer's in- and out-projection
SHORTCONV_PROJ_SCOPE = "shortconv/proj"
#: the split into ``B | C | u``, the gate ``B * u``, the taps, the gate ``C *``
SHORTCONV_MIX_SCOPE = "shortconv/mix"
# -- hyper-connected residual streams (models/mpt.py): ``jax.named_scope``s in
# every operation's ``op_name``, and one counter in the train step's metrics --
#: a sublayer's three maps: the flattened norm, its projection, the squashes
#: and the mixing matrix's Sinkhorn iterations
MHC_MAPS_SCOPE = "mhc/maps"
#: the sublayer's input read out of the streams (and the model's exit sum)
MHC_READ_IN_SCOPE = "mhc/read_in"
#: the streams mixed and the branch written back into each
MHC_WRITE_BACK_SCOPE = "mhc/write_back"
#: the largest distance from 1 of a row or column sum of any sublayer's
#: mixing matrix in the step (0 = doubly stochastic), fetched with the loss
MHC_SINKHORN_GAP = "mhc/sinkhorn_gap"
#: opened and closed inside the fence like ``trainer/moe_load``, only when the
#: step's blocks are hyper-connected: attr ``sinkhorn_gap``
TRAINER_MHC_SPAN = "trainer/mhc"
#: the spans inside ``trainer/fence``, in the order ``Trainer.fit`` opens
#: them, each with its ``{attr: step metric}``: one is opened when the last
#: step returned a metric of its, and a static count of the model's
#: (``models/step.step_attrs``) under an attr listed here joins what ``fit``
#: returns under that metric's name
FENCE_SPANS: dict[str, dict[str, str]] = {
    TRAINER_MOE_LOAD_SPAN: {
        "rows_held": MOE_ROWS_HELD, "max_expert_load": MOE_MAX_EXPERT_LOAD,
        "dispatch_rows_moved": MOE_DISPATCH_ROWS_MOVED,
        "dispatch_rows_static": MOE_DISPATCH_ROWS_STATIC},
    TRAINER_MHC_SPAN: {"sinkhorn_gap": MHC_SINKHORN_GAP},
    TRAINER_DSA_SPAN: {
        "picked_pairs": DSA_PICKED_PAIRS, "causal_pairs": DSA_CAUSAL_PAIRS,
        "tiles_visited": DSA_TILES_VISITED, "tiles_causal": DSA_TILES_CAUSAL,
        "index_loss": DSA_INDEX_LOSS},
}
# -- every block (models/mpt.py) and the step around them
# (train/train_step.py): ``jax.named_scope``s like the families' above, so
# that no device time of a step is left to a bare instruction name --------
#: the non-expert MLP: its products, the activation between them, the
#: residual add (the leading dense block of an expert model too; the expert
#: layer keeps ``moe/*``)
BLOCK_MLP_SCOPE = "block/mlp"
#: attention's projections on the non-latent branch (``wqkv`` or ``q_proj`` /
#: ``k_proj`` / ``v_proj``, the reshapes, the rotation, ``out_proj`` and its
#: residual add); the latent branch keeps ``mla/proj``
ATTN_PROJ_SCOPE = "attn/proj"
#: the flash kernel's three WINDOWED launches (``ops/flash_attention.py``: the
#: sliding-window layers' forward, dq and dk/dv, which walk the band), as the
#: kernel's name stands in a launch's ``op_name`` before ``/multihead_attention``;
#: the causal launches keep ``flash_fwd`` / ``flash_dq`` / ``flash_dkv``
FLASH_SWA_FWD_KERNEL = "flash_swa_fwd"
FLASH_SWA_DQ_KERNEL = "flash_swa_dq"
FLASH_SWA_DKV_KERNEL = "flash_swa_dkv"
#: the headwise gate on attention's output (``attn_gate``): its ``[D, H]``
#: product, the sigmoid and the multiply
ATTN_GATE_SCOPE = "attn/gate"
#: the block norms ``ln_1`` / ``ln_2`` and the model's final ``ln_f``
BLOCK_NORM_SCOPE = "block/norm"
#: the gradient's global norm and the logged norm of the new weights
GRAD_NORM_SCOPE = "train_step/grad_norm"

# -- transport-leg span names (federation/tcp.py; spans only, never KPIs) --
TCP_SEND_SPAN = "tcp/send"
TCP_RECV_SPAN = "tcp/recv"
# -- parameter-plane span names (federation/transport.py): the plane write,
# read and release alone, whoever calls; attrs mode / nbytes / wire_nbytes
TRANSPORT_PUT_SPAN = "transport/put"
TRANSPORT_GET_SPAN = "transport/get"
TRANSPORT_FREE_SPAN = "transport/free"
#: a node letting the previous broadcast go once the new one is read (on the
#: shm plane the last reference un-maps the old segment); a leaf, attr mode
TRANSPORT_UNMAP_SPAN = "transport/unmap"

# -- serving plane (photon_tpu/serve, ISSUE 5) ----------------------------
# KPIs the continuous batcher records into its own History (exported via
# telemetry/prom.py's exposition renderer on the frontend's /metrics):
#: seconds from request admission-queue entry to its FIRST streamed token
SERVE_TTFT_S = "serve/ttft_s"
#: decoded tokens/sec across the slot batch over the last scheduler tick
SERVE_TOKENS_PER_S = "serve/tokens_per_s"
#: admission-queue depth at tick time (backpressure: full queue → HTTP 429)
SERVE_QUEUE_DEPTH = "serve/queue_depth"
#: fraction of decode slots occupied at tick time
SERVE_SLOT_OCCUPANCY = "serve/slot_occupancy"
#: cumulative finished sequences evicted from slots (EOS / length cap)
SERVE_EVICTIONS = "serve/evictions"
#: cumulative requests rejected at admission (queue full → 429)
SERVE_REJECTED = "serve/rejected"
# span-only request phases (telemetry plane): the per-request umbrella and
# its queue/prefill/decode children, emitted at request completion
SERVE_REQUEST_SPAN = "serve/request"
SERVE_QUEUE_SPAN = "serve/queue"
SERVE_PREFILL_SPAN = "serve/prefill"
SERVE_DECODE_SPAN = "serve/decode"

# -- multi-tenant serving daemon (ISSUE 11, serve/prefix.py + hotswap.py) --
# Content-addressed prefix cache (tick-time gauges/counters recorded into
# the batcher History AND mirrored onto the typed hub):
#: fraction of cumulative prompt tokens served out of the prefix cache
#: (cached full-block tokens / all submitted prompt tokens)
SERVE_PREFIX_HIT_RATE = "serve/prefix_hit_rate"
#: physical blocks currently indexed by the prefix cache (each holds one
#: allocator reference; shared CoW blocks in live use count here too)
SERVE_PREFIX_SHARED_BLOCKS = "serve/prefix_shared_blocks"
#: cumulative cache entries dropped (LRU pressure + explicit flushes)
SERVE_PREFIX_EVICTIONS = "serve/prefix_evictions"
#: cumulative prompt tokens whose prefill was skipped via a cache hit
SERVE_PREFIX_TOKENS_CACHED = "serve/prefix_tokens_cached_total"
# Live checkpoint hot-swap (serve/hotswap.py watcher + scheduler swap point):
#: cumulative parameter swaps applied at the scheduler swap point
SERVE_HOTSWAP_SWAPS_TOTAL = "serve/hotswap_swaps_total"
#: seconds from swap request to the reference assignment landing (the
#: quiesce window: running slots finishing on the old params)
SERVE_HOTSWAP_SWAP_LATENCY_S = "serve/hotswap_swap_latency_s"
#: candidate rounds the watcher refused because their manifest checksums
#: failed (the corrupt round is skipped-and-warned, never swapped)
SERVE_HOTSWAP_REJECTED_CORRUPT = "serve/hotswap_rejected_corrupt_total"
#: the server round currently being served (moves on a successful swap)
SERVE_HOTSWAP_ROUND = "serve/hotswap_round"
# span-only: the swap window (request → reference assignment)
SERVE_HOTSWAP_SWAP_SPAN = "serve/hotswap_swap"

# -- ragged paged attention + chunked prefill (ISSUE 12) ------------------
# Attention-plane gauges (tick-time, from PagedEngine.attn_stats):
#: the live attention walk width in BLOCKS (the monotone high-water
#: pow2 bucket; == the full table width under attention_impl=gather)
SERVE_ATTN_CTX_BLOCKS = "serve/attn_ctx_blocks"
#: fraction of the paged pool's blocks currently allocated (live KV)
SERVE_ATTN_LIVE_FRAC = "serve/attn_live_frac"
#: 1.0 when the ragged live-block walk is active, 0.0 under the
#: full-width dense-gather oracle path (attention_impl=gather)
SERVE_ATTN_RAGGED = "serve/attn_ragged"
# Chunked-prefill counters (scheduler-owned, cumulative):
#: scheduler steps that carried a prompt chunk alongside decode rows
SERVE_CHUNK_STEPS = "serve/chunk_steps_total"
#: prompt tokens prefilled through the chunk stream
SERVE_CHUNK_TOKENS = "serve/chunk_tokens_total"
#: prompts that needed more than one chunk (suffix > the per-step
#: token budget — the giant prompts that used to monopolize a step)
SERVE_CHUNK_SPLIT_PROMPTS = "serve/chunk_split_prompts_total"

# -- speculative decoding (ISSUE 15, serve/draft.py) -----------------------
# Draft-and-verify counters (scheduler-owned, cumulative):
#: draft tokens proposed to the verification grid
SERVE_SPEC_DRAFTED = "serve/spec_drafted_total"
#: draft tokens the model accepted (longest-matching-prefix for greedy,
#: rejection-sampling for temperature rows)
SERVE_SPEC_ACCEPTED = "serve/spec_accepted_total"
#: scheduler steps that carried at least one drafted row
SERVE_SPEC_STEPS = "serve/spec_steps_total"
# Tick-time gauges:
#: the accept-rate EWMA driving the auto-throttle (1.0 = every draft lands)
SERVE_SPEC_ACCEPT_RATE = "serve/spec_accept_rate"
#: the throttle's current per-row draft depth K (0 = plain decode)
SERVE_SPEC_K = "serve/spec_k"

# -- per-cohort LoRA personalization plane (ISSUE 13, photon_tpu/adapters) --
# Train side (federation/collective_round.py grouped rounds):
#: cohorts whose adapters updated this round (fused grouped reduction OR
#: the per-cohort host fold on the degraded path)
ADAPTER_COHORTS = "server/adapter_cohorts"
#: configured cohorts with ZERO surviving members this round — their
#: adapters stayed untouched (per-cohort degradation: one cohort's dead
#: clients never cost another cohort its round)
ADAPTER_COHORTS_DEGRADED = "server/adapter_cohorts_degraded"
#: modeled cross-slice bytes of this round's ADAPTER exchange (the
#: ~1000x-under-full-model number the personalization plane exists for;
#: equals server/collective_wire_bytes on adapter rounds)
ADAPTER_WIRE_BYTES = "server/adapter_wire_bytes"
# Serve side (serve/adapter_pool.py, tick-time from engine.adapter_stats):
#: adapter pages currently resident on device
SERVE_ADAPTER_RESIDENTS = "serve/adapter_residents"
#: cohorts in the host bank (servable cohorts)
SERVE_ADAPTER_COHORTS = "serve/adapter_cohorts"
#: cumulative host→device page loads (cohort misses)
SERVE_ADAPTER_LOADS = "serve/adapter_loads_total"
#: cumulative page evictions (LRU pressure on the pool)
SERVE_ADAPTER_EVICTIONS = "serve/adapter_evictions_total"
#: fraction of cohort acquisitions served by a resident page
SERVE_ADAPTER_HIT_RATE = "serve/adapter_hit_rate"

# -- fleet router (ISSUE 16, serve/router.py + serve/fleet.py) ------------
# Router-tier KPIs recorded into the router's own History (exported via the
# same exposition renderer on the router's /metrics):
#: cumulative /generate requests the router accepted for routing
ROUTER_REQUESTS_TOTAL = "router/requests_total"
#: requests placed by the chain-hash prefix-affinity key
ROUTER_ROUTED_PREFIX = "router/routed_prefix_total"
#: requests placed by the sticky cohort pin
ROUTER_ROUTED_COHORT = "router/routed_cohort_total"
#: requests placed by the power-of-two-choices queue-depth fallback
ROUTER_ROUTED_P2C = "router/routed_p2c_total"
#: requests re-placed on a survivor after a connect failure (never after
#: response bytes started flowing — those surface to the client)
ROUTER_REROUTES = "router/reroutes_total"
#: cumulative proxy legs that failed outright (no survivor accepted)
ROUTER_PROXY_ERRORS = "router/proxy_errors_total"
#: replicas the liveness ladder currently counts live / suspect / dead
ROUTER_REPLICAS_LIVE = "router/replicas_live"
ROUTER_REPLICAS_SUSPECT = "router/replicas_suspect"
ROUTER_REPLICAS_DEAD = "router/replicas_dead"
#: cumulative cohort pins moved off a dead replica onto a survivor
ROUTER_COHORT_REPINS = "router/cohort_repins_total"
# Fleet-supervisor KPIs (serve plane vocabulary — the replicas are serve
# daemons; the supervisor aggregates):
#: replica daemons the supervisor currently manages
SERVE_FLEET_REPLICAS = "serve/fleet_replicas"
#: cumulative one-at-a-time rolling hot-swap passes across the fleet
SERVE_FLEET_ROLLING_SWAPS = "serve/fleet_rolling_swaps_total"

# -- run-health observatory instruments (ISSUE 10, telemetry/metrics.py) --
# Histogram instruments on the serve plane (typed-metric hub, NOT History
# KPIs: a latest-value gauge can't show a distribution):
#: seconds per OUTPUT token after the first (decode cadence; the serving
#: latency number TTFT doesn't cover)
SERVE_TPOT_S = "serve/tpot_s"
#: seconds a request waited in the admission queue before a slot opened
SERVE_QUEUE_WAIT_S = "serve/queue_wait_s"

# Device-plane introspection KPIs (telemetry/introspect.py), sampled at
# round boundaries (server/*) and serve-tick boundaries (serve/*):
#: live device (HBM) bytes on the first local device
HBM_BYTES_IN_USE = "server/hbm_bytes_in_use"
#: peak device bytes since process start
HBM_PEAK_BYTES = "server/hbm_peak_bytes"
#: cumulative backend compiles this process (program-cache misses are
#: visible as this counter moving in steady state)
COMPILES_TOTAL = "server/backend_compiles_total"
SERVE_HBM_BYTES_IN_USE = "serve/hbm_bytes_in_use"
SERVE_HBM_PEAK_BYTES = "serve/hbm_peak_bytes"
SERVE_COMPILES_TOTAL = "serve/backend_compiles_total"

# Instrument-only names (never History KPIs): transport frame sizes and
# the observability-of-the-observability drop counter.
#: TCP control-plane frame bytes, send leg (histogram)
TCP_SEND_BYTES = "tcp/send_bytes"
#: TCP control-plane frame bytes, recv leg (histogram)
TCP_RECV_BYTES = "tcp/recv_bytes"
#: spans discarded by the bounded tracer buffer (counter; also the kind of
#: the once-per-run warning event emitted on the first drop)
SPANS_DROPPED = "telemetry/spans_dropped"

# -- structured event kinds (telemetry/events.py JSONL log) ---------------
# Event names are registry constants for the same reason KPI/span names
# are: photon-lint's kpi-registry rule flags any string literal at an
# emit_event site, so a typo'd event kind can't silently fork the
# vocabulary consumers (export.py, dashboards) query by.
#: every LivenessTracker state-machine edge, incl. first registration
EVENT_MEMBERSHIP_TRANSITION = "membership/transition"
#: node agent redialed the server (supervisor loop, federation/tcp.py)
EVENT_TCP_RECONNECT = "tcp/reconnect"
#: CRC32 frame-check failure tore a connection down
EVENT_TCP_CORRUPT_FRAME = "tcp/corrupt_frame"
#: SpeedMonitor resolved its bf16 peak (device_kind + basis for MFU)
EVENT_SPEED_MONITOR_PEAK = "speed_monitor/peak"
#: a collective participant missed a stage deadline / failed its fit and
#: was dropped from the round's cohort (ISSUE 8)
EVENT_COLLECTIVE_STRAGGLER = "collective/straggler"
#: the gang was rebuilt over the surviving cohort mid-round
EVENT_COLLECTIVE_RECONFIG = "collective/reconfig"
#: the round degraded to the host-plane aggregate_inplace fold
EVENT_COLLECTIVE_DEGRADED = "collective/degraded"
#: fault-injector firings are ``chaos/<plan kind>`` (chaos/injector.py
#: counters: tcp_drop, store_bitflip, crash, ...)
CHAOS_EVENT_PREFIX = "chaos/"
#: a configured adapter cohort had no surviving member this round — its
#: adapter skipped the update while every other cohort proceeded
EVENT_ADAPTER_COHORT_DEGRADED = "adapter/cohort_degraded"
#: the hot-swap watcher applied a new round's params (ISSUE 11)
EVENT_HOTSWAP_SWAPPED = "hotswap/swapped"
#: the watcher skipped a candidate round (corrupt manifest, failing
#: federation health, or a poll landing during drain) — attrs say which
EVENT_HOTSWAP_SKIPPED = "hotswap/skipped"
#: a replica registered with the fleet router (HELLO + fleet_report)
EVENT_FLEET_REPLICA_UP = "fleet/replica_up"
#: the liveness ladder declared a replica dead; its cohorts re-pin
EVENT_FLEET_REPLICA_DEAD = "fleet/replica_dead"
#: a cohort's sticky pin moved to a survivor (attrs: cohort, from, to)
EVENT_FLEET_COHORT_REPIN = "fleet/cohort_repin"
#: one replica finished its leg of a rolling hot-swap pass
EVENT_FLEET_ROLLING_SWAP = "fleet/rolling_swap"
#: async server advanced its version clock (attrs: version, arrivals,
#: staleness_max — the ISSUE 18 analog of a completed round)
EVENT_ASYNC_VERSION = "async/version_advance"
#: a delta arrived staler than max_staleness and was rejected; the client
#: was re-dispatched from a fresh version (attrs: cid, staleness)
EVENT_ASYNC_REJECT = "async/stale_reject"
#: a LivenessTracker dead edge dropped a client's in-flight delta before
#: it could fold (attrs: cid)
EVENT_ASYNC_DROP = "async/delta_dropped"
#: the buffer filled but < min_arrivals distinct clients had landed — the
#: version clock held (stall-not-abort; attrs: buffered, distinct)
EVENT_ASYNC_STALL = "async/min_arrivals_stall"

# -- SLO autopilot (ISSUE 19, telemetry/autopilot.py) ----------------------
# Every controller decision is an event carrying the rule that fired, the
# observed metric value, and the old/new knob values — the audit trail the
# chaos storm e2e and /statusz both read.
#: a rule breached its target and tightened its knob (attrs: rule, knob,
#: observed, old, new)
EVENT_AUTOPILOT_ACTUATION = "autopilot/actuation"
#: a rule's breach cleared for relax_after evaluations and the knob probed
#: back toward the subsystem's declared value (same attrs)
EVENT_AUTOPILOT_RELAX = "autopilot/relax"
#: a breach persisted but the knob was already at its bound — emitted once
#: per saturation episode, never repeated per evaluation
EVENT_AUTOPILOT_SATURATED = "autopilot/saturated"
#: knob-id gauges (the autopilot mirrors every knob it owns into the hub
#: so dashboards can overlay actuations on the metrics that drove them):
AUTOPILOT_KNOB_PREFILL_BUDGET = "serve/prefill_token_budget"
AUTOPILOT_KNOB_SPEC_K_MAX = "serve/spec_k_max"
AUTOPILOT_KNOB_STAGE_TIMEOUT_S = "server/collective_stage_timeout_s"
AUTOPILOT_KNOB_QUANT_LEVEL = "server/collective_quantization_level"
AUTOPILOT_KNOB_MAX_STALENESS = "server/async_max_staleness"
#: one-shot actions (no continuous knob value; the event's old/new carry
#: the action's before/after observation, e.g. free blocks):
AUTOPILOT_ACTION_RECLAIM = "serve/memory_reclaim"
AUTOPILOT_ACTION_RESTART = "fleet/restart_replica"
#: controller KPI counters/gauges:
AUTOPILOT_ACTUATIONS = "server/autopilot_actuations_total"
AUTOPILOT_RELAXES = "server/autopilot_relaxes_total"
AUTOPILOT_SATURATIONS = "server/autopilot_saturations_total"
AUTOPILOT_RULES_BREACHED = "server/autopilot_rules_breached"
#: per-round straggler fraction mirrored into the hub at the collective
#: tick site (the series the straggler_deadline rule takes its p90 over)
COLLECTIVE_STRAGGLER_FRAC = "server/collective_straggler_frac"

# -- structured alert kinds (telemetry/health.py, ISSUE 10) ---------------
# Health watchers emit these as events (same registry discipline) AND
# record them on the monitor's alert tail rolled up into /statusz.
#: NaN/Inf in the round's aggregated KPI dict (delta norm, server loss)
ALERT_NONFINITE = "alert/nonfinite"
#: straggler-percentile watcher over the collective cohort
ALERT_STRAGGLERS = "alert/stragglers"
#: a collective round on the degradation ladder / budget exhausted
ALERT_DEGRADED_ROUNDS = "alert/degraded_rounds"
#: serve admission queue pinned at its bound
ALERT_QUEUE_SATURATION = "alert/queue_saturation"
#: checkpoint-plane corruption (corrupt round skipped at resume)
ALERT_STORE_CORRUPT = "alert/store_corrupt"
#: live HBM growing monotonically across a full sample window
ALERT_HBM_GROWTH = "alert/hbm_growth"
#: an adapter cohort lost every member for a round (personalization
#: plane degradation — scoped to that cohort only, ISSUE 13)
ALERT_ADAPTER_COHORT = "alert/adapter_cohort"
#: a fleet replica went dead on the liveness ladder (ISSUE 16): the
#: fleet degrades by 1/N and its cohorts re-pin to survivors
ALERT_FLEET_REPLICA_DEAD = "alert/fleet_replica_dead"

#: dynamic metric-name families the registry can't enumerate statically:
#: per-strategy-state norms (``server/{state_key}_norm``,
#: strategy/base.py:norm_telemetry). Patterns are re.fullmatch'd.
DYNAMIC_METRIC_PATTERNS: tuple[str, ...] = (r"server/[A-Za-z0-9_]+_norm",)


def registered_metric_names() -> frozenset:
    """Every ``server/*`` / ``client/*`` / ``serve/*`` / ``router/*`` name
    declared as a module constant (the static half of the registry; see
    DYNAMIC_METRIC_PATTERNS)."""
    import sys

    mod = sys.modules[__name__]
    return frozenset(
        v
        for k, v in vars(mod).items()
        if isinstance(v, str)
        and not k.startswith("_")
        and (v.startswith("server/") or v.startswith("client/")
             or v.startswith("serve/") or v.startswith("router/"))
    )


def is_registered_metric(name: str) -> bool:
    import re

    if name in registered_metric_names():
        return True
    return any(re.fullmatch(p, name) for p in DYNAMIC_METRIC_PATTERNS)


# Host-plane round-pipeline KPI names (PR 2). Recorded into the round
# metrics by the strategy / server so the History tracks where the host
# seconds between device rounds actually go:
#: fetch + dequantize seconds of the streaming aggregation (summed across
#: pool workers — can exceed wall-clock on the pipelined path)
AGG_DECODE_TIME = "server/agg_decode_time"
#: fused fold seconds of the streaming aggregation
AGG_FOLD_TIME = "server/agg_fold_time"
#: duration of the most recently COMPLETED background checkpoint write
#: (round N's metrics carry round N-1's write; 0.0 until one completes)
CKPT_ASYNC_WRITE_S = "server/ckpt_async_write_s"

# Elastic-membership KPI names (ISSUE 3): recorded every round by ServerApp
# from the LivenessTracker + the drivers' HELLO stats.
#: nodes the liveness state machine currently counts as live
NODES_LIVE = "server/nodes_live"
#: nodes with missed pings, not yet declared dead
NODES_SUSPECT = "server/nodes_suspect"
#: nodes declared dead (out of rotation until they re-register)
NODES_DEAD = "server/nodes_dead"
#: readmissions THIS round (dead/crashed nodes back in rotation)
NODES_READMITTED = "server/nodes_readmitted"
#: cumulative node-reported redial backoff seconds (from HELLO payloads)
RECONNECT_BACKOFF_S = "server/reconnect_backoff_s"


@dataclasses.dataclass
class WireStats:
    """Bytes-on-wire accounting for the parameter plane.

    ``raw`` is what the payload would cost uncompressed (its metadata's
    ``total_bytes``), ``wire`` what actually moved; ``sent`` covers
    :meth:`ParamTransport.put` (server: broadcasts; client: fit results),
    ``recv`` covers :meth:`ParamTransport.get`. On the SERVER transport the
    recv counters are therefore the uplink — the path the compression
    subsystem exists for.
    """

    sent_raw_bytes: int = 0
    sent_wire_bytes: int = 0
    recv_raw_bytes: int = 0
    recv_wire_bytes: int = 0
    n_sent: int = 0
    n_recv: int = 0

    def record_sent(self, raw: int, wire: int) -> None:
        self.sent_raw_bytes += int(raw)
        self.sent_wire_bytes += int(wire)
        self.n_sent += 1

    def record_recv(self, raw: int, wire: int) -> None:
        self.recv_raw_bytes += int(raw)
        self.recv_wire_bytes += int(wire)
        self.n_recv += 1

    def snapshot(self) -> "WireStats":
        return dataclasses.replace(self)

    def metrics_since(self, prev: "WireStats") -> dict[str, float]:
        """Round-delta metrics (recorded into History by the round loop):
        uplink raw/wire bytes + compression ratio, downlink (broadcast)
        wire bytes."""
        up_raw = self.recv_raw_bytes - prev.recv_raw_bytes
        up_wire = self.recv_wire_bytes - prev.recv_wire_bytes
        down_wire = self.sent_wire_bytes - prev.sent_wire_bytes
        out = {
            WIRE_UPLINK_RAW_BYTES: float(up_raw),
            WIRE_UPLINK_BYTES: float(up_wire),
            WIRE_BROADCAST_BYTES: float(down_wire),
        }
        if up_wire > 0:
            out[WIRE_COMPRESSION_RATIO] = up_raw / up_wire
        return out

TPU_V5E_PEAK_FLOPS = 197e12  # bf16
TPU_V4_PEAK_FLOPS = 275e12
A100_PEAK_FLOPS = 312e12

# bf16 peak FLOPs by device_kind substring (first match wins; most-specific
# first). Used to turn tokens/sec into MFU for whatever chip the run lands
# on — including GPU hosts (jax device_kind is e.g. "NVIDIA A100-SXM4-40GB").
# A kind that is not in the table (CPU, emulators) has no peak: it is an
# error, not a default.
PEAK_FLOPS_BY_DEVICE_KIND: list[tuple[str, float]] = [
    ("v6", 918e12),
    ("v5p", 459e12),
    ("v5e", TPU_V5E_PEAK_FLOPS),
    ("v5 lite", TPU_V5E_PEAK_FLOPS),
    ("v5litepod", TPU_V5E_PEAK_FLOPS),
    ("v5", 459e12),  # bare "TPU v5" (no lite marker) = v5p
    ("v4", TPU_V4_PEAK_FLOPS),
    ("v3", 123e12),
    ("v2", 45e12),
    ("h100", 989e12),  # SXM dense bf16
    ("a100", A100_PEAK_FLOPS),
]


def peak_flops_for_device_kind(kind: str, default: float | None = None) -> float:
    """bf16 peak FLOP/s of one chip of ``kind``. Raises on a kind the table
    does not hold unless the caller passes a ``default`` of its own."""
    low = kind.lower()
    peak = next((p for sub, p in PEAK_FLOPS_BY_DEVICE_KIND if sub in low), default)
    if peak is None:
        raise ValueError(
            f"no bf16 peak known for device_kind {kind!r} "
            "(see PEAK_FLOPS_BY_DEVICE_KIND)"
        )
    return peak


def is_oom(e: BaseException) -> bool:
    """Device-memory exhaustion, any backend's phrasing."""
    msg = str(e)
    return "RESOURCE_EXHAUSTED" in msg or "out of memory" in msg.lower()


def dump_memory_profile(save_dir: str, tag: str = "oom") -> str | None:
    """Write ``jax.profiler.device_memory_profile()`` (a pprof protobuf) to
    ``save_dir/memory_{tag}_{ts}.prof`` — the MemorySnapshot/OOMObserver
    analog (reference wires torch memory tooling with remote upload,
    ``photon/clients/trainer_utils.py:721-729``). Round 2 of this build was
    blind on exactly an OOM; this leaves the allocation picture on disk.
    Best-effort: returns the path or None."""
    import pathlib
    import time as _time

    try:
        import jax

        data = jax.profiler.device_memory_profile()
        out = pathlib.Path(save_dir)
        out.mkdir(parents=True, exist_ok=True)
        path = out / f"memory_{tag}_{_time.strftime('%Y%m%dT%H%M%SZ', _time.gmtime())}.prof"
        path.write_bytes(data)
        return str(path)
    except Exception:  # noqa: BLE001 — diagnostics must never mask the OOM
        return None


def _sparse_attention_flops_per_token(cfg: ModelConfig) -> float:
    """The terms of ``benchmark/costs/keye_sparse_moe_train.py`` at the
    expected counts (``tests/test_keye_sparse.py`` holds the two equal): the
    attention over the pairs a selection without ties picks (``min(t + 1,
    topk)`` a query), not over the causal half; the indexer's projections at
    4 operations a weight (their input is detached: no input gradient), its
    scores once over every causal pair and their two backward products over
    the picked pairs; router, the routed experts at this chip's expected
    share, and the head."""
    d, L, s, v = cfg.d_model, cfg.n_layers, cfg.max_seq_len, cfg.vocab_size
    h, g, dh = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    j, di, k = cfg.dsa_index_heads, cfg.dsa_index_head_dim, min(cfg.dsa_topk, s)
    picked = L * (k * (k + 1) / 2.0 + (s - k) * k) / s
    causal = L * (s + 1) / 2.0
    rows = L * cfg.moe_top_k * cfg.experts_held / cfg.moe_num_experts
    return (6.0 * L * (d * (h + 2 * g) * dh + h * dh * d)
            + 4.0 * L * d * (j * di + di + j)
            + causal * j * 2 * di + 2.0 * picked * j * 2 * di
            + 3.0 * picked * h * 4 * dh
            + 6.0 * L * d * cfg.moe_num_experts
            + 3.0 * rows * 3 * 2 * d * cfg.mlp_hidden_size
            + 6.0 * d * v)


def _kinds_flops_per_token(cfg: ModelConfig) -> float:
    """The terms of ``benchmark/costs/laguna_swa_moe_train.py`` at the expected
    counts (``tests/test_laguna_swa.py`` holds the two equal): every attention
    layer by its kind (``ModelConfig.attention_kind``: projections at its own
    head count, the headwise gate's ``[D, H]`` product, the score and value
    products over the pairs it sees: a full layer's causal half, a sliding
    layer's band), the leading dense layers, the router, the shared expert,
    the routed experts at this chip's expected share, and the head. Layers
    that are not attention are not this count's."""
    d, L, s, v = cfg.d_model, cfg.n_layers, cfg.max_seq_len, cfg.vocab_size
    n_kv, dh = cfg.n_kv_heads or cfg.n_heads, cfg.d_head
    kinds = cfg.layer_kinds or ("attention",) * L
    total = 0.0
    for name in kinds:
        kind = cfg.attention_kind(name)
        h, w = kind.n_heads, min(kind.window or s, s)
        pairs = w * (w + 1) / 2.0 + (s - w) * w  # query t sees min(t + 1, w) keys
        total += 6.0 * (d * (h + 2 * n_kv) * dh + h * dh * d)
        total += 6.0 * d * h if cfg.attn_gate else 0.0
        total += 3.0 * pairs / s * h * 4 * dh
    n_dense = cfg.first_k_dense
    total += 6.0 * n_dense * 3 * d * cfg.dense_mlp_hidden_size
    hidden = cfg.mlp_hidden_size or cfg.expansion_ratio * d
    if cfg.dropless_moe:
        rows = cfg.moe_top_k * cfg.experts_held / cfg.moe_num_experts
        total += (L - n_dense) * 6.0 * (
            d * cfg.moe_num_experts + (rows + cfg.moe_shared_experts) * 3 * d * hidden)
    else:
        total += (L - n_dense) * 6.0 * (3 if cfg.mlp == "swiglu" else 2) * d * hidden
    return total + 6.0 * d * v


def _single_branch_flops_per_token(cfg: ModelConfig) -> float:
    """The terms of ``benchmark/costs/nemotron_h_moe_train.py`` at the expected
    counts (``tests/test_nemotron_h.py`` holds the two equal), a layer by its
    one branch: a Mamba-2 mixer's two projections, its taps, and the scan's
    products with ``C B^T`` once a group; an attention layer's projections and
    the causal half of its score and value products; an expert layer's router,
    its shared expert and the routed experts at this chip's expected share,
    two matrices each where they are ungated; and the head."""
    d, s, v = cfg.d_model, cfg.max_seq_len, cfg.vocab_size
    hidden = cfg.mlp_hidden_size or cfg.expansion_ratio * d
    n_kv, h, dh = cfg.n_kv_heads or cfg.n_heads, cfg.n_heads, cfg.d_head
    inner, n, g = cfg.mamba_d_inner, cfg.mamba_d_state, cfg.mamba_n_groups
    q, matrices = cfg.mamba_chunk_size, 3 if cfg.moe_gated else 2
    pairs = (q + 1) / 2.0  # earlier positions of its chunk a position meets
    mamba = (6.0 * (d * (2 * inner + 2 * g * n + cfg.mamba_n_heads) + inner * d)
             + 3.0 * 2 * cfg.mamba_d_conv * (inner + 2 * g * n)
             + 3.0 * (pairs * 2 * (g * n + inner) + 4 * inner * n))
    attention = 6.0 * (d * (h + 2 * n_kv) * dh + h * dh * d) + 3.0 * (s + 1) / 2.0 * h * 4 * dh
    rows = cfg.moe_top_k * cfg.experts_held / cfg.moe_num_experts
    moe = 6.0 * (d * cfg.moe_num_experts + matrices * d * (
        rows * hidden + cfg.shared_expert_width))
    kinds = cfg.layer_kinds
    return (kinds.count("mamba") * mamba + kinds.count("attention") * attention
            + cfg.moe_layers * moe + 6.0 * d * v)


def model_flops_per_token(cfg: ModelConfig) -> float:
    """Training FLOPs/token ≈ 6·N_nonemb + 12·L·d·s (attention) + 6·d·V
    (lm_head, tied or not). Matches the estimate used for BASELINE
    vs_baseline; honors the llama-family knobs (``mlp_hidden_size``
    override, SwiGLU's third projection). Latent attention counts its
    low-rank pairs and the causal half of its 256-wide heads; leading dense
    blocks their own width; the dropless expert layer its router, shared
    experts and the routed experts at this chip's expected share (``top_k *
    held / routed`` experts a token): what the step computes here, not what
    the whole model would. A Mamba-2 layer (``layer_types``) counts its two
    projections and the chunked scan's products in attention's place, a
    ``conv`` layer its two projections, its taps and its gates. Hyper-
    connected streams (``hc_mult``) count their maps' projection, two a
    layer; their mixing is elementwise and bound by bytes, not counted.
    Learned sparse attention (``dsa_topk``) has a count of its own,
    :func:`_sparse_attention_flops_per_token`, and so have layers of one
    branch (``single_branch_layers``), :func:`_single_branch_flops_per_token`."""
    d, L, s, v = cfg.d_model, cfg.n_layers, cfg.max_seq_len, cfg.vocab_size
    if cfg.sparse_attention:
        return _sparse_attention_flops_per_token(cfg)
    if cfg.single_branch_layers:
        return _single_branch_flops_per_token(cfg)
    hidden = cfg.mlp_hidden_size or cfg.expansion_ratio * d
    if cfg.dropless_moe:
        experts = cfg.moe_top_k * cfg.experts_held / cfg.moe_num_experts
        mlp_w = (experts + cfg.moe_shared_experts) * 3 * d * hidden + d * cfg.moe_num_experts
    else:
        # gelu: up+down = 2·d·F weights; swiglu adds the gate = 3·d·F
        mlp_w = (3 if cfg.mlp == "swiglu" else 2) * d * hidden
    if cfg.latent_attention:
        qk, dv = cfg.d_head, cfg.v_head_dim
        attn_w = (d * cfg.q_lora_rank + cfg.q_lora_rank * cfg.n_heads * qk
                  + d * (cfg.kv_lora_rank + cfg.qk_rope_head_dim)
                  + cfg.kv_lora_rank * cfg.n_heads * (cfg.qk_nope_head_dim + dv)
                  + cfg.n_heads * dv * d)
        attn = 6 * L * s * cfg.n_heads * (qk + dv) / 2  # causal half, fwd+bwd
    elif cfg.swa_layers or cfg.attn_gate:
        # full and sliding layers, each kind's heads: a full layer's causal
        # half, a sliding layer's band (``min(t + 1, window)`` keys a query);
        # the gate's product a head
        return _kinds_flops_per_token(cfg)
    else:
        # GQA shrinks the kv projections: q + 2·kv groups + out_proj
        n_kv = cfg.n_kv_heads or cfg.n_heads
        attn_w = d * (cfg.n_heads + 2 * n_kv) * cfg.d_head + d * d
        attn = 12 * L * d * s  # score + value matmuls, fwd+bwd
    # another mixer stands in attention's place in these layers
    n_mamba, n_conv = cfg.mamba_layers, cfg.conv_layers
    mamba_w = 0
    if n_mamba:
        inner, n, q = cfg.mamba_d_inner, cfg.mamba_d_state, cfg.mamba_chunk_size
        mamba_w = d * (2 * inner + 2 * n + cfg.mamba_n_heads) + inner * d
        # forward products a token: C B^T and the masked square at their causal
        # half, the chunk's state and its read-out; three times with the backward
        scan = 3 * (q * n + q * inner + 4 * inner * n)
        attn = attn * (L - n_mamba) / L + n_mamba * scan
    if n_conv:
        # the taps and both gates are elementwise: 2 operations a tap and
        # channel, two gates, three times with the backward
        attn = attn * (L - n_conv) / L + n_conv * 3 * (2 * cfg.conv_kernel_size + 2) * d
    conv_w = 3 * d * d + d * d  # B | C | u, and the projection back
    n_dense = cfg.first_k_dense  # leading SwiGLU blocks of their own width
    n_block = ((L - n_mamba - n_conv) * attn_w + n_mamba * mamba_w + n_conv * conv_w
               + n_dense * 3 * d * cfg.dense_mlp_hidden_size + (L - n_dense) * mlp_w)
    if cfg.hyper_connected:
        n = cfg.hc_mult
        n_block += 2 * L * n * d * (2 * n + n * n)
    head = 6 * d * v
    return 6.0 * n_block + attn + head


class SpeedMonitor:
    """EMA tokens/sec + MFU (reference: llm-foundry ``speed_monitor``
    callback, ``mpt-125m.yaml:98-109``).

    ``peak_flops=None`` (the default) looks the bf16 peak up from
    ``device_kind`` — or, when that is also None, from
    ``jax.devices()[0].device_kind`` — via :func:`peak_flops_for_device_kind`.
    A device the table does not know has no peak: the monitor then reports
    throughput and NO ``throughput/mfu`` (``peak_flops_per_chip`` is None).
    The resolved kind/peak are kept on :attr:`device_kind` /
    :attr:`peak_flops_per_chip` so callers can record the choice as a run
    attribute/event."""

    def __init__(self, cfg: ModelConfig, peak_flops: float | None = None,
                 n_chips: int = 1, alpha: float = 0.9,
                 device_kind: str | None = None) -> None:
        self.flops_per_token = model_flops_per_token(cfg)
        if peak_flops is None:
            if device_kind is None:
                import jax

                device_kind = jax.devices()[0].device_kind
            try:
                peak_flops = peak_flops_for_device_kind(device_kind)
            except ValueError:
                peak_flops = None
        self.device_kind = device_kind or ""
        self.peak_flops_per_chip = None if peak_flops is None else float(peak_flops)
        self.n_chips = n_chips
        self.peak = None if peak_flops is None else peak_flops * n_chips
        self.alpha = alpha
        self._ema = 0.0
        self._t = 0

    def update(self, tokens: int, seconds: float) -> dict[str, float]:
        if seconds <= 0:
            return {}
        tps = tokens / seconds
        self._t += 1
        self._ema = self.alpha * self._ema + (1 - self.alpha) * tps
        ema = self._ema / (1 - self.alpha**self._t)
        out = {
            "throughput/tokens_per_sec": tps,
            "throughput/tokens_per_sec_ema": ema,
        }
        if self.peak is not None:
            out["throughput/mfu"] = tps * self.flops_per_token / self.peak
        return out
