"""Device-resident aggregation plane: hierarchical ICI/DCN collectives.

The marquee TPU-native path (SURVEY.md §7 stage 6): where the reference moves
every client's full parameter list through S3/shm/Ray and averages on the
server CPU (``strategy/aggregation.py:44-118``, ``s3_utils.py:730-1115``),
TPU slices that are part of one ``jax.distributed`` job aggregate with XLA
collectives — no host round-trip, no object store, bandwidth = wire speed of
ICI/DCN.

Three layers, each the degenerate case of the next:

1. **Flat fp32 psum** (:func:`collective_weighted_average`): a 1-D
   ``clients`` mesh, one weighted ``psum`` per pytree leaf. The original
   path; every program below reproduces it bit-exactly at ``replica=1`` /
   ``quantization="off"``.
2. **Hierarchical two-stage reduce** (:func:`hierarchical_weighted_average`):
   a 2-D ``(clients, replica)`` mesh (:func:`make_hierarchical_mesh`) where
   ``clients`` is the cross-slice DCN axis and ``replica`` the intra-slice
   ICI axis. Each client's contribution is reduce-scattered over ICI (each
   of the ``replica`` ranks owns ``1/replica`` of the flat vector), the
   cross-slice reduction runs per-rank over DCN (``replica`` parallel
   exchanges of ``1/replica`` the bytes — the classic hierarchical
   allreduce), and an ICI all-gather reassembles the replicated result.
3. **Quantized cross-slice exchange** (``quantization="q8"``): the DCN leg
   ships blockwise-int8 codes + fp32 per-block scales instead of fp32
   (EQuARX, PAPERS.md) — reduce-scatter → q8 encode → all-gather exchange →
   dequant-accumulate → ICI all-gather. The codec is the jnp port of
   ``compression/quantize.py`` (shared ``DEFAULT_BLOCK``/``_QMAX``; parity
   pinned byte-exact), so the wire-plane error analysis carries over: per
   element the cross-slice average errs by at most
   ``Σ_c scale_c/2`` where ``scale_c = absmax(block of w_c·x_c)/127`` —
   each client's rounding contributes ``scale/2`` per hop and the single
   dequant-accumulate hop sums them. Modeled DCN bytes drop ~3.94x at the
   default block of 256 (1 + 4/256 bytes/value vs 4).

On top rides the **device-resident server optimizer**
(:class:`DeviceAggregationPlane`): the average → pseudo-gradient →
FedAvgEff/Nesterov/FedMom/FedAdam/FedYogi update runs fused in the SAME
jitted SPMD program, with optimizer state living as replicated device
arrays. ``strategy/optimizers.py`` stays the host oracle — the device rules
mirror it op-for-op (tests pin parity bit-exact at ``off`` given the same
average) and checkpoints round-trip through the existing host
``Strategy.state_for_checkpoint``.

Programs are built once per (mesh, structure, policy) and cached — a fresh
``shard_map`` per round would retrace every round, which the PR 6
``RetraceSentinel`` e2e now forbids from round 2.

Numerics: weights ``n_i / Σn`` are computed in fp32 from per-client sample
counts; the weighted sum runs in fp32 regardless of param dtype — matching
the reference's float accumulation (``aggregate_inplace``).
"""

from __future__ import annotations

import threading
import time
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from photon_tpu.compression.quantize import COLLECTIVE_QUANTIZATIONS, DEFAULT_BLOCK
from photon_tpu.compression.quantize_jnp import quantize_q8_jnp

CLIENT_AXIS = "clients"
REPLICA_AXIS = "replica"


def _full_shard_map(f: Callable, mesh: Mesh, in_specs, out_specs) -> Callable:
    """Full-manual shard_map (every mesh axis manual), replication checking
    off."""
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False,
    )


# ---------------------------------------------------------------------------
# meshes
# ---------------------------------------------------------------------------


def make_client_mesh(n_clients: int, devices: list | None = None) -> Mesh:
    """1-D mesh with one entry per client slice-representative.

    Multi-host: call after ``jax.distributed.initialize`` with the global
    device list (one device per slice, e.g. each slice's device 0). The same
    SPMD program then runs on every host and XLA routes the psum over DCN.
    """
    devices = devices if devices is not None else jax.devices()
    if len(devices) < n_clients:
        raise ValueError(f"need {n_clients} devices for the client axis, have {len(devices)}")
    return Mesh(np.asarray(devices[:n_clients]), (CLIENT_AXIS,))


def make_hierarchical_mesh(
    n_clients: int, replica: int = 1, devices: list | None = None
) -> Mesh:
    """2-D ``(clients, replica)`` mesh: row c = client c's slice (its
    ``replica`` ICI-connected chips), column axis = intra-slice ranks.

    Multi-host: each process contributes its slice's devices contiguously so
    row c lands on the process that owns cid c (the same device-order
    contract as :func:`make_client_mesh`; see
    ``CollectiveFedRunner._default_mesh``). ``replica=1`` is the degenerate
    flat topology — same participant set as :func:`make_client_mesh`, and
    the ``off`` average is pinned bit-exact against it.
    """
    if replica < 1:
        raise ValueError(f"replica must be >= 1, got {replica}")
    devices = devices if devices is not None else jax.devices()
    need = n_clients * replica
    if len(devices) < need:
        raise ValueError(
            f"need {need} devices for a ({n_clients}, {replica}) client mesh, "
            f"have {len(devices)}"
        )
    grid = np.asarray(devices[:need]).reshape(n_clients, replica)
    return Mesh(grid, (CLIENT_AXIS, REPLICA_AXIS))


def mesh_replica(mesh: Mesh) -> int:
    """ICI width of a client mesh (1 on the flat 1-D topology)."""
    return int(mesh.shape[REPLICA_AXIS]) if REPLICA_AXIS in mesh.axis_names else 1


# ---------------------------------------------------------------------------
# hierarchical weighted average (the collective core)
# ---------------------------------------------------------------------------


def _check_one_row(ns_shape: tuple) -> None:
    # make_*_mesh pins exactly one client per mesh row; the numerators below
    # read only row 0, so a mesh packing >1 row per shard would drop clients
    # while still counting their samples — fail loudly (trace-time check:
    # shard shapes are static).
    if ns_shape[0] != 1:
        raise ValueError(
            f"collective aggregation expects 1 client row per device shard, "
            f"got {ns_shape[0]} — repack the client mesh"
        )


def _chunk_len(n: int, replica: int, quantization: str, block: int) -> int:
    """Per-rank chunk length of one flattened leaf under the reduce-scatter
    layout: block-aligned on the q8 policy (the encode never sees a ragged
    tail inside the collective), plain ceil-division otherwise. The SAME
    function sizes the sharded optimizer-state layout (ZeRO-1, ISSUE 14),
    so the state shards line up with the reduce-scatter output by
    construction — and because block boundaries stay aligned to the global
    padded vector for every ``replica``, the q8 scales (and therefore the
    averaged values) are bit-identical across a resharding."""
    if quantization == "q8":
        return -(-n // (replica * block)) * block
    return -(-n // replica)


def _make_reduce_to_shard(mesh: Mesh, quantization: str, block: int) -> Callable:
    """Cross-client reduction of one leaf's weighted contribution, returning
    THIS RANK's chunk of the summed flat vector — the ICI reduce-scatter +
    (optionally q8) DCN leg of :func:`_make_reduce_leaf` WITHOUT the trailing
    ICI all-gather. The ZeRO-1 plane (ISSUE 14) consumes the shard directly:
    the server update runs on it and only the updated params are gathered."""
    n_clients = int(mesh.shape[CLIENT_AXIS])
    replica = mesh_replica(mesh)
    has_replica = REPLICA_AXIS in mesh.axis_names

    def _reduce_to_shard(contrib: jnp.ndarray) -> jnp.ndarray:
        flat = contrib.reshape(-1)
        n = flat.size
        chunk = _chunk_len(n, replica, quantization, block)
        pad = replica * chunk - n
        if pad:
            flat = jnp.concatenate([flat, jnp.zeros((pad,), jnp.float32)])
        if has_replica:
            # ICI reduce-scatter: rank r keeps chunk r of its slice's
            # contribution (the row is replicated intra-slice, so the
            # "reduce" is chunk selection; a data-parallel client whose
            # ranks hold partials would psum over REPLICA_AXIS first)
            r = jax.lax.axis_index(REPLICA_AXIS)
            mychunk = jax.lax.dynamic_slice(flat, (r * chunk,), (chunk,))
        else:
            mychunk = flat
        if quantization == "q8":
            # cross-slice DCN leg: int8 codes + fp32/block scales on the
            # wire instead of fp32 values (EQuARX)
            codes, scales = quantize_q8_jnp(mychunk, block)
            all_codes = jax.lax.all_gather(codes, CLIENT_AXIS)
            all_scales = jax.lax.all_gather(scales, CLIENT_AXIS)
            grid = all_codes.astype(jnp.float32).reshape(
                n_clients, chunk // block, block
            )
            # dequant-accumulate: deterministic sum over the client axis
            red = (grid * all_scales[:, :, None]).sum(axis=0).reshape(-1)
        else:
            red = jax.lax.psum(mychunk, CLIENT_AXIS)
        return red

    return _reduce_to_shard


def _make_reduce_leaf(mesh: Mesh, quantization: str, block: int) -> Callable:
    """Shared cross-client reduction body (flat psum / hierarchical
    two-stage / q8 DCN leg) — the single construction point for the plain
    weighted average AND the grouped per-cohort average (ISSUE 13), so the
    grouped program inherits the exact wire semantics (and error bounds)
    the PR 7 plane pinned."""
    replica = mesh_replica(mesh)
    has_replica = REPLICA_AXIS in mesh.axis_names
    _reduce_to_shard = _make_reduce_to_shard(mesh, quantization, block)

    def _reduce_leaf(contrib: jnp.ndarray) -> jnp.ndarray:
        """Weighted per-client contribution (one full row, replicated over
        the ICI axis) → cross-client sum, replicated."""
        shape = contrib.shape
        if replica == 1 and quantization == "off":
            # degenerate flat path: one fp32 psum, bit-compatible with the
            # original 1-D program
            return jax.lax.psum(contrib, CLIENT_AXIS)
        n = contrib.size
        red = _reduce_to_shard(contrib)
        if has_replica:
            # ICI all-gather reassembles the full replicated vector
            red = jax.lax.all_gather(red, REPLICA_AXIS, tiled=True)
        return red[:n].reshape(shape)

    return _reduce_leaf


def _build_average_local(
    mesh: Mesh, quantization: str, block: int
) -> Callable:
    """The per-device body of the (hierarchical, optionally quantized)
    weighted average. Closure constants only — no traced branches."""
    _reduce_leaf = _make_reduce_leaf(mesh, quantization, block)

    def local(ns, *leaves):
        # ns: [1] local sample count; leaves: [1, ...] rows (see
        # _check_one_row); everything replicated along REPLICA_AXIS.
        _check_one_row(ns.shape)
        n_total = jax.lax.psum(jnp.sum(ns.astype(jnp.float32)), CLIENT_AXIS)
        w = ns[0].astype(jnp.float32) / n_total
        outs = tuple(
            _reduce_leaf(leaf[0].astype(jnp.float32) * w) for leaf in leaves
        )
        return outs + (n_total,)

    return local


def _mapped_average(
    mesh: Mesh, n_leaves: int, quantization: str, block: int
) -> Callable:
    """shard_map-wrapped (unjitted) average over ``n_leaves`` stacked leaves
    plus the Σn psum — the single construction point for both the cached
    standalone program and the fused device-optimizer program."""
    local = _build_average_local(mesh, quantization, block)
    return _full_shard_map(
        local,
        mesh,
        in_specs=(P(CLIENT_AXIS),) + tuple(P(CLIENT_AXIS) for _ in range(n_leaves)),
        out_specs=tuple(P() for _ in range(n_leaves)) + (P(),),
    )


#: (mesh, n_leaves, quantization, block) → jitted average program. Programs
#: must be built once and reused: a fresh shard_map wrapper per call would
#: retrace (and backend-compile) every round.
_AVG_PROGRAMS: dict[tuple, Callable] = {}


def _average_program(
    mesh: Mesh, n_leaves: int, quantization: str, block: int
) -> Callable:
    key = (mesh, n_leaves, quantization, block)
    prog = _AVG_PROGRAMS.get(key)
    if prog is None:
        prog = jax.jit(_mapped_average(mesh, n_leaves, quantization, block))
        _AVG_PROGRAMS[key] = prog
    return prog


#: (mesh, n_arrays) → jitted ICI all-gather program reassembling flat
#: REPLICA_AXIS-sharded arrays into replicated ones (the ZeRO-1 plane's
#: post-update params gather and the checkpoint-time state gather). Cached
#: for the same reason as _AVG_PROGRAMS: a fresh shard_map per call would
#: retrace every round.
_GATHER_PROGRAMS: dict[tuple, Callable] = {}


def _gather_program(mesh: Mesh, n_arrays: int) -> Callable:
    key = (mesh, n_arrays)
    prog = _GATHER_PROGRAMS.get(key)
    if prog is None:

        def local(*xs):
            return tuple(
                jax.lax.all_gather(x, REPLICA_AXIS, tiled=True) for x in xs
            )

        mapped = _full_shard_map(
            local,
            mesh,
            in_specs=tuple(P(REPLICA_AXIS) for _ in range(n_arrays)),
            out_specs=tuple(P() for _ in range(n_arrays)),
        )
        prog = _GATHER_PROGRAMS[key] = jax.jit(mapped)
    return prog


def evict_mesh_programs(mesh: Mesh) -> None:
    """Drop every cached average program built over ``mesh``. Pair with
    evicting the mesh itself (e.g. the collective runner's bounded
    cohort-mesh cache): a jitted executable pins device memory for the
    process lifetime otherwise."""
    for key in [k for k in _AVG_PROGRAMS if k[0] is mesh]:
        del _AVG_PROGRAMS[key]
    for key in [k for k in _GROUPED_PROGRAMS if k[0] is mesh]:
        del _GROUPED_PROGRAMS[key]
    for key in [k for k in _GATHER_PROGRAMS if k[0] is mesh]:
        del _GATHER_PROGRAMS[key]


# ---------------------------------------------------------------------------
# grouped (per-cohort) weighted average — ISSUE 13
# ---------------------------------------------------------------------------


def _build_grouped_local(
    mesh: Mesh, n_cohorts: int, quantization: str, block: int
) -> Callable:
    """Per-device body of the fused multi-cohort reduction: every client
    contributes its row weighted into its OWN cohort's slot of a
    ``[n_cohorts, ...]`` stack, and ONE cross-client reduction (the same
    hierarchical / optionally-q8 body as the plain average) lands every
    cohort's sample-weighted mean in a single program — K cohorts cost one
    collective rendezvous, not K. Adapter payloads are tiny, so the K-fold
    stack stays far below one full-model exchange."""
    _reduce_leaf = _make_reduce_leaf(mesh, quantization, block)

    def local(ns, onehot, *leaves):
        # ns: [1] local sample count; onehot: [1, K] this client's cohort
        # row; leaves: [1, ...] rows — all sharded on the client axis.
        _check_one_row(ns.shape)
        n = ns[0].astype(jnp.float32)
        # per-cohort Σn rides the same program (one psum): cohorts with no
        # surviving member total 0 — their slot averages to exactly 0 and
        # the CALLER must skip them (max() only guards the division)
        totals = jax.lax.psum(n * onehot[0], CLIENT_AXIS)  # [K]
        w = onehot[0] * (n / jnp.maximum(totals, 1.0))  # [K] cohort weights
        outs = []
        for leaf in leaves:
            row = leaf[0].astype(jnp.float32)
            contrib = w.reshape((n_cohorts,) + (1,) * row.ndim) * row[None]
            outs.append(_reduce_leaf(contrib))
        return tuple(outs) + (totals,)

    return local


#: (mesh, n_leaves, n_cohorts, quantization, block) → jitted grouped
#: program; same build-once discipline as _AVG_PROGRAMS (a fresh shard_map
#: per round would retrace, which the sentinel e2e forbids)
_GROUPED_PROGRAMS: dict[tuple, Callable] = {}


def _grouped_program(
    mesh: Mesh, n_leaves: int, n_cohorts: int, quantization: str, block: int
) -> Callable:
    key = (mesh, n_leaves, n_cohorts, quantization, block)
    prog = _GROUPED_PROGRAMS.get(key)
    if prog is None:
        local = _build_grouped_local(mesh, n_cohorts, quantization, block)
        mapped = _full_shard_map(
            local,
            mesh,
            in_specs=(P(CLIENT_AXIS), P(CLIENT_AXIS))
            + tuple(P(CLIENT_AXIS) for _ in range(n_leaves)),
            out_specs=tuple(P() for _ in range(n_leaves)) + (P(),),
        )
        prog = _GROUPED_PROGRAMS[key] = jax.jit(mapped)
    return prog


def grouped_weighted_average(
    stacked_flat: Sequence[jax.Array],
    n_samples: jax.Array,
    cohort_onehot: jax.Array,
    mesh: Mesh,
    quantization: str = "off",
    block: int = DEFAULT_BLOCK,
) -> tuple[list[jax.Array], jax.Array]:
    """Sample-weighted PER-COHORT averages over the client axis in ONE
    fused program (ISSUE 13: all cohorts' reductions batched into a single
    rendezvous on the PR 7 plane).

    ``stacked_flat``: flat leaves ``[n_clients, ...]`` sharded on the
    client axis (each client's adapter row). ``n_samples``:
    ``[n_clients] int``. ``cohort_onehot``: ``[n_clients, n_cohorts]``
    0/1 assignment (a client in no cohort is an all-zero row and
    contributes nowhere). Returns ``([K, ...] fp32 averaged leaves,
    replicated, and the per-cohort Σn [K])`` — a cohort whose total is 0
    had no surviving member this round; its average slot is meaningless
    zeros and callers must leave that cohort's state untouched."""
    if quantization not in COLLECTIVE_QUANTIZATIONS:
        raise ValueError(
            f"quantization must be one of {COLLECTIVE_QUANTIZATIONS}, got "
            f"{quantization!r}"
        )
    if block < 1:
        raise ValueError(f"block must be >= 1, got {block}")
    n_cohorts = int(cohort_onehot.shape[1])
    if n_cohorts < 1:
        raise ValueError("need at least one cohort column")
    prog = _grouped_program(
        mesh, len(stacked_flat), n_cohorts, quantization, block
    )
    out = prog(n_samples, cohort_onehot, *stacked_flat)
    return list(out[:-1]), out[-1]


def hierarchical_weighted_average(
    stacked_params: Any,
    n_samples: jax.Array,
    mesh: Mesh,
    quantization: str = "off",
    block: int = DEFAULT_BLOCK,
    return_total: bool = False,
) -> Any:
    """Sample-weighted average over the client axis, hierarchical over the
    replica (ICI) axis when the mesh has one, optionally int8-quantized on
    the cross-slice (DCN) leg.

    ``stacked_params``: pytree whose leaves are ``[n_clients, ...]`` arrays
    sharded on the client axis (each slice contributes its row).
    ``n_samples``: ``[n_clients] int`` sharded likewise.
    Returns the averaged pytree (leaves ``[...]`` fp32, replicated) — every
    slice ends the round holding identical new globals, which also replaces
    the reference's post-aggregation broadcast (``broadcast_utils.py``).
    With ``return_total`` the replicated Σn rides the SAME program as one
    extra psum output (callers need it for metrics; a separate collective
    per round would be a second rendezvous).
    """
    if quantization not in COLLECTIVE_QUANTIZATIONS:
        raise ValueError(
            f"quantization must be one of {COLLECTIVE_QUANTIZATIONS}, got "
            f"{quantization!r}"
        )
    if block < 1:
        # callers resolve the config's 0-means-default sentinel before here
        # (CollectiveFedRunner.q8_block); 0 would otherwise die as a bare
        # ZeroDivisionError in the chunk math
        raise ValueError(f"block must be >= 1, got {block}")
    flat, treedef = jax.tree_util.tree_flatten(stacked_params)
    prog = _average_program(mesh, len(flat), quantization, block)
    out_flat = prog(n_samples, *flat)
    avg = jax.tree_util.tree_unflatten(treedef, list(out_flat[:-1]))
    if return_total:
        return avg, out_flat[-1]
    return avg


def staleness_discount(
    staleness, policy: str = "poly", power: float = 1.0
):
    """Staleness-discount multiplier d(s) for the async buffered server
    (ISSUE 18): ``poly`` → ``(1 + s)^(−power)`` (the FedAsync polynomial),
    ``const`` → 1.0. Vectorized over numpy inputs; d(0) == 1.0 EXACTLY
    under both policies — the zero-staleness bit-parity regime."""
    s = np.asarray(staleness, np.float64)
    if np.any(s < 0):
        raise ValueError("staleness must be >= 0")
    if policy == "const":
        return np.ones_like(s)
    if policy == "poly":
        return (1.0 + s) ** (-float(power))
    raise ValueError(f"staleness_policy must be 'poly' or 'const', got {policy!r}")


def discounted_fold_weights(
    n_samples: Sequence[int],
    staleness: Sequence[int],
    policy: str = "poly",
    power: float = 1.0,
) -> np.ndarray:
    """Per-client fold weights ``n_i · d(s_i)`` for the discounted entry.

    When every discount is exactly 1 (all-fresh buffer, or the const
    policy) the weights come back **int32** — the same dtype the
    synchronous round feeds the fused program, so the async fold reuses
    the already-compiled sync executable and its result is bit-for-bit
    the sync round's. Any real discount switches to float32 (one extra
    compile, absorbed at warmup like every other program variant)."""
    ns = np.asarray(n_samples)
    d = staleness_discount(staleness, policy, power)
    if np.all(d == 1.0):
        return ns.astype(np.int32)
    return (ns.astype(np.float64) * d).astype(np.float32)


def discounted_weighted_average(
    stacked_params: Any,
    n_samples: Sequence[int],
    staleness: Sequence[int],
    mesh: Mesh,
    policy: str = "poly",
    power: float = 1.0,
    quantization: str = "off",
    block: int = DEFAULT_BLOCK,
    return_total: bool = False,
) -> Any:
    """Staleness-discounted weighted average (the ISSUE 18 fold entry):
    identical program to :func:`hierarchical_weighted_average` with weights
    pre-scaled by d(staleness) on host — the device body already casts its
    weight row to fp32, so discounting costs nothing on device and
    degenerates bit-exactly to the plain average at zero staleness (see
    :func:`discounted_fold_weights`). ``return_total`` yields Σ n·d — the
    effective sample mass behind this version, which is what the
    discounted mean normalizes by."""
    w = discounted_fold_weights(n_samples, staleness, policy, power)
    return hierarchical_weighted_average(
        stacked_params,
        jax.device_put(w, NamedSharding(mesh, P(CLIENT_AXIS))),
        mesh,
        quantization=quantization,
        block=block,
        return_total=return_total,
    )


def collective_weighted_average(
    stacked_params: Any,
    n_samples: jax.Array,
    mesh: Mesh,
    return_total: bool = False,
) -> Any:
    """The flat fp32 average (``quantization="off"``) — kept as the stable
    entry point; on a hierarchical mesh it runs the two-stage reduce."""
    return hierarchical_weighted_average(
        stacked_params, n_samples, mesh, quantization="off",
        return_total=return_total,
    )


def collective_fedavg_round(
    stacked_params: Any,
    global_params: Any,
    n_samples: jax.Array,
    mesh: Mesh,
    server_lr: float = 1.0,
) -> Any:
    """Stateless FedAvgEff round on device: weighted average →
    pseudo-gradient → server SGD step (``x ← x − η(x − avg)``). With
    ``server_lr=1`` this is exact FedAvg. Stateful server optimizers run
    through :class:`DeviceAggregationPlane` instead (fused average + update
    + device-resident state)."""
    avg = collective_weighted_average(stacked_params, n_samples, mesh)
    return jax.tree.map(
        lambda x, a: (x.astype(jnp.float32) - server_lr * (x.astype(jnp.float32) - a)).astype(x.dtype),
        global_params,
        avg,
    )


def stack_for_clients(host_params_per_client: list[Any], mesh: Mesh) -> Any:
    """Host-side helper (tests / single-host): stack per-client pytrees into
    client-axis-sharded device arrays (replicated along the replica axis)."""
    stacked = jax.tree.map(lambda *xs: np.stack(xs), *host_params_per_client)
    sharding = NamedSharding(mesh, P(CLIENT_AXIS))
    return jax.tree.map(lambda x: jax.device_put(x, sharding), stacked)


# ---------------------------------------------------------------------------
# modeled DCN cost
# ---------------------------------------------------------------------------


def modeled_cross_slice_bytes(
    sizes: Sequence[int],
    n_clients: int,
    replica: int = 1,
    quantization: str = "off",
    block: int = DEFAULT_BLOCK,
) -> int:
    """Idealized bytes crossing slice boundaries for one aggregation round:
    every client's (padded) contribution crosses DCN exactly once, summed
    over clients — algorithm-independent (a ring all-gather moves
    ``(C-1)/C`` of this per participant; a tree psum about the same), so
    the fp32-vs-q8 RATIO is what the model is for. ``sizes`` are per-leaf
    element counts. The hierarchy (``replica``) splits the exchange across
    ICI ranks without changing the total, exactly as on hardware."""
    total = 0
    for n in sizes:
        n = int(n)
        if quantization == "q8":
            chunk = -(-n // (replica * block)) * block
            padded = replica * chunk
            total += padded + (padded // block) * 4
        else:
            total += -(-n // replica) * replica * 4
    return total * int(n_clients)


# ---------------------------------------------------------------------------
# device-resident server optimizers (fused with the average)
# ---------------------------------------------------------------------------

#: strategy.name → state tensor lists the device plane carries (mirrors
#: ``strategy/optimizers.py`` ``state_keys``)
DEVICE_RULES: dict[str, tuple[str, ...]] = {
    "fedavg": (),
    "nesterov": ("momentum",),
    "fedmom": ("momentum",),
    "fedadam": ("momentum_1", "momentum_2"),
    "fedyogi": ("momentum_1", "momentum_2"),
}


def device_server_update(
    rule: str,
    params: Sequence[jnp.ndarray],
    grads: Sequence[jnp.ndarray],
    state: dict[str, Sequence[jnp.ndarray]],
    lr: jnp.ndarray,
    b1t: jnp.ndarray,
    b2t: jnp.ndarray,
    momentum: float = 0.0,
    beta_1: float = 0.9,
    beta_2: float = 0.99,
    tau: float = 1.0e-9,
) -> tuple[list[jnp.ndarray], dict[str, list[jnp.ndarray]]]:
    """jnp port of the five host update rules, op-for-op
    (``strategy/optimizers.py`` is the oracle; parity tests pin each rule
    bit-exact on CPU given the same average). ``g`` is the pseudo-gradient
    ``x − avg``; ``b1t``/``b2t`` are the host-computed bias corrections
    ``1 − β^t`` (fp64 on host, cast to fp32 exactly as numpy casts its
    python-float scalars) so the adaptive rules stay retrace-free — the
    round counter never enters the traced program as a Python int."""
    if rule == "fedavg":
        return [x - lr * g for x, g in zip(params, grads)], {}
    if rule in ("nesterov", "fedmom"):
        new_m = [momentum * m + g for m, g in zip(state["momentum"], grads)]
        if rule == "nesterov":
            new_p = [
                x - lr * (g + momentum * m)
                for x, g, m in zip(params, grads, new_m)
            ]
        else:
            new_p = [x - lr * m for x, m in zip(params, new_m)]
        return new_p, {"momentum": new_m}
    if rule not in ("fedadam", "fedyogi"):
        raise ValueError(f"no device update rule for strategy {rule!r}")
    new_m1 = [
        beta_1 * m + (1.0 - beta_1) * g
        for m, g in zip(state["momentum_1"], grads)
    ]
    if rule == "fedadam":
        new_m2 = [
            beta_2 * v + (1.0 - beta_2) * jnp.square(g)
            for v, g in zip(state["momentum_2"], grads)
        ]
    else:
        new_m2 = []
        for v, g in zip(state["momentum_2"], grads):
            g2 = jnp.square(g)
            new_m2.append(v - (1.0 - beta_2) * g2 * jnp.sign(v - g2))
    new_p = [
        x - lr * (m / b1t) / (jnp.sqrt(v / b2t) + tau)
        for x, m, v in zip(params, new_m1, new_m2)
    ]
    return new_p, {"momentum_1": new_m1, "momentum_2": new_m2}


class DeviceAggregationPlane:
    """The fused server round as ONE jitted SPMD program: hierarchical
    (optionally q8-quantized) weighted average → pseudo-gradient → server
    optimizer update.

    **ZeRO-1 sharding (ISSUE 14, the default).** With ``sharded=True``,
    parameters and optimizer moments live between rounds as padded-and-
    flattened fp32 device arrays sharded ``P(REPLICA_AXIS)`` — each ICI
    rank owns ``1/replica`` of every leaf (the exact reduce-scatter chunk
    layout, :func:`_chunk_len`). The round program keeps the weighted
    average's reduce-scatter output ON the rank's shard: pseudo-gradient,
    all five update rules, the q8 ``nonneg_rows`` clamp and the norm
    telemetry all run sharded, and ONE ICI all-gather reassembles only the
    updated params (after the update — grounded in "Automatic Cross-Replica
    Sharding of Weight Update in Data-Parallel Training", PAPERS.md). Per-
    rank server-state HBM and update FLOPs divide by ``replica`` instead of
    replicating; the update arithmetic is elementwise, so the sharded round
    is bit-identical to the replicated one (pinned by test), and because
    the padded-flat layout is value-preserving, checkpoints round-trip
    bit-exactly across a resharding (save at replica=4, resume at
    replica=1, and vice versa). ``sharded=False`` keeps the PR 7 replicated
    layout (still the right call at ``replica=1`` or for tiny models —
    PERF.md).

    The host :class:`~photon_tpu.strategy.base.Strategy` instance supplies
    the rule name + hyperparameters and stays the checkpoint authority:
    :meth:`sync_strategy` pushes the device state (and the adaptive ``_t``
    counter) back into it so ``Strategy.state_for_checkpoint`` round-trips
    unchanged, and a strategy restored from a checkpoint seeds a fresh
    plane via the constructor (bias-correction continuity pinned by test).
    """

    def __init__(
        self,
        mesh: Mesh,
        strategy: Any,
        quantization: str = "off",
        block: int = DEFAULT_BLOCK,
        nonneg_rows: Sequence[int] = (),
        sharded: bool = True,
    ) -> None:
        if strategy.name not in DEVICE_RULES:
            raise ValueError(
                f"strategy {strategy.name!r} has no device update rule "
                f"(supported: {sorted(DEVICE_RULES)})"
            )
        if quantization not in COLLECTIVE_QUANTIZATIONS:
            raise ValueError(
                f"quantization must be one of {COLLECTIVE_QUANTIZATIONS}, "
                f"got {quantization!r}"
            )
        if block < 1:
            raise ValueError(f"block must be >= 1, got {block}")
        if strategy.current_parameters is None:
            raise RuntimeError("strategy not initialized with parameters")
        self.mesh = mesh
        self.rule = strategy.name
        self.quantization = quantization
        self.block = int(block)
        self.state_keys = tuple(strategy.state_keys)
        self.n_clients = int(mesh.shape[CLIENT_AXIS])
        self.hyper = {
            "momentum": float(strategy.momentum),
            "beta_1": float(getattr(strategy, "beta_1", 0.9)),
            "beta_2": float(getattr(strategy, "beta_2", 0.99)),
            "tau": float(getattr(strategy, "tau", 1.0e-9)),
        }
        self.adaptive = self.rule in ("fedadam", "fedyogi")
        #: server-update step counter (adaptive bias correction); seeded
        #: from a restored strategy so resume keeps ``1 − β^t`` continuous
        self.t = int(getattr(strategy, "_t", 0))
        self._replicated = NamedSharding(mesh, P())
        self.sharded = bool(sharded)
        self.replica = mesh_replica(mesh)
        self._has_replica = REPLICA_AXIS in mesh.axis_names
        #: the between-rounds layout of the sharded plane: P(REPLICA_AXIS)
        #: over the padded flat vector (replicated across the client axis);
        #: degenerates to replicated on a flat 1-D client mesh
        self._shard_sharding = NamedSharding(
            mesh, P(REPLICA_AXIS) if self._has_replica else P()
        )
        #: per-leaf layout metadata (shared by seeding, the fused program,
        #: the host bridges and the byte accounting): original shape/size
        #: and the per-rank chunk length of the padded flat layout
        self._shapes = [tuple(np.shape(p)) for p in strategy.current_parameters]
        self._sizes = [int(np.prod(s, dtype=np.int64)) for s in self._shapes]
        self._chunks = [
            _chunk_len(n, self.replica, quantization, int(block))
            for n in self._sizes
        ]
        #: wall seconds of the last post-update params all-gather + fetch
        #: (``server/opt_allgather_time``; 0 until the first params_host)
        self.last_allgather_s = 0.0
        n_rows = len(strategy.current_parameters)
        if any(not 0 <= int(i) < n_rows for i in nonneg_rows):
            raise ValueError(
                f"nonneg_rows out of range for a {n_rows}-row payload: "
                f"{sorted(int(i) for i in nonneg_rows)}"
            )
        #: payload rows that must stay >= 0 (aggregated second moments in a
        #: [params|m1|m2] payload). Only enforced on the q8 path: at `off`
        #: the pseudo-gradient of an all-zero m2 element is exactly zero so
        #: the adaptive rules leave it alone, but q8 rounding noise makes it
        #: tiny-nonzero and the sign-like adaptive step then kicks the
        #: element by ~lr — negative second moments NaN the clients'
        #: sqrt(m2) on the next fit. Clamping at `off` would break the
        #: bit-exact pins against the host oracle, which does not clamp.
        self.nonneg_rows = tuple(sorted({int(i) for i in nonneg_rows}))
        self._seed_from_host(strategy)
        self._program: Callable | None = None
        # abandon-epoch (ISSUE 8): bumped when the caller gives up on an
        # in-flight run_round (missed stage deadline); a late-completing
        # abandoned run must not commit params/state/t under the round that
        # replaced it. The lock makes the worker's check-and-commit atomic
        # with abandon()/reseed_from(): an abandon can't slip between the
        # epoch check and the last field assignment, and a reseed can't
        # interleave with a stale commit's writes.
        self._epoch = 0
        self._commit_lock = threading.Lock()

    def _put_leaf_sharded(self, leaf: np.ndarray | None, i: int) -> jax.Array:
        """Seed ONE leaf directly into its target padded-flat sharded layout
        (``None`` = zero-fill, for missing optimizer state). No intermediate
        full-size host copy is materialized (ISSUE 14 satellite): the
        callback hands jax per-shard views of the flat leaf, and only a
        shard that straddles the padding (or a zero leaf) allocates — one
        chunk at a time, so peak host RSS during plane construction is
        O(largest chunk), not O(payload). Pinned by a tracemalloc test."""
        n, chunk = self._sizes[i], self._chunks[i]
        padded = self.replica * chunk
        flat = None
        if leaf is not None:
            flat = np.asarray(leaf, np.float32).reshape(-1)

        def cb(index):
            sl = index[0] if index else slice(None)
            start = sl.start or 0
            stop = padded if sl.stop is None else sl.stop
            if flat is None:
                # all-zero shards are identical: every one aliases the SAME
                # read-only buffer (device arrays are immutable, the buffer
                # is never written) — one chunk of host RSS, not one per
                # shard per tensor
                return self._zero_chunk(stop - start)
            if stop <= n:
                return flat[start:stop]  # a view — no copy
            out = np.zeros(stop - start, np.float32)
            if start < n:
                out[: n - start] = flat[start:n]
            return out

        return jax.make_array_from_callback((padded,), self._shard_sharding, cb)

    def _zero_chunk(self, length: int) -> np.ndarray:
        """Shared zero buffer for zero-filled shards (views of one
        allocation; callers must treat it as read-only — it may be aliased
        into many device arrays on the CPU backend)."""
        buf = getattr(self, "_zero_buf", None)
        if buf is None or buf.size < length:
            self._zero_buf = buf = np.zeros(
                max(length, max(self._chunks, default=0)), np.float32
            )
        return buf[:length]

    def _seed_from_host(self, strategy: Any) -> None:
        """Device-put params + optimizer state from the host strategy (the
        single seeding point shared by ``__init__`` and
        :meth:`reseed_from`); missing state keys seed zero-filled. On the
        sharded (ZeRO-1) plane every leaf lands directly in its padded-flat
        ``P(REPLICA_AXIS)`` layout via :meth:`_put_leaf_sharded`."""
        if self.sharded:
            self.params = [
                self._put_leaf_sharded(p, i)
                for i, p in enumerate(strategy.current_parameters)
            ]
            self.state = {}
            for key in self.state_keys:
                host = strategy.state.get(key)
                self.state[key] = [
                    self._put_leaf_sharded(
                        host[i] if host is not None else None, i
                    )
                    for i in range(len(self._sizes))
                ]
            return
        self.params = [
            jax.device_put(np.asarray(p, np.float32), self._replicated)
            for p in strategy.current_parameters
        ]
        self.state = {}
        for key in self.state_keys:
            host = strategy.state.get(key)
            if host is None:
                host = [np.zeros_like(np.asarray(p, np.float32))
                        for p in strategy.current_parameters]
            self.state[key] = [
                jax.device_put(np.asarray(a, np.float32), self._replicated)
                for a in host
            ]

    # -- the fused program -------------------------------------------------
    def _build_program(self, n_leaves: int) -> Callable:
        mapped = _mapped_average(self.mesh, n_leaves, self.quantization, self.block)
        rule, hyper = self.rule, dict(self.hyper)
        clamp_rows = (
            frozenset(self.nonneg_rows) if self.quantization == "q8" else frozenset()
        )

        def program(ns, stacked, params, state, lr, b1t, b2t):
            out = mapped(ns, *stacked)
            avgs, n_total = out[:-1], out[-1]
            grads = [x - a for x, a in zip(params, avgs)]
            new_params, new_state = device_server_update(
                rule, params, grads, state, lr, b1t, b2t, **hyper
            )
            if clamp_rows:
                # restore the second-moment invariant the q8 noise breaks
                # (see __init__)
                new_params = [
                    jnp.maximum(p, 0.0) if i in clamp_rows else p
                    for i, p in enumerate(new_params)
                ]
            # norm telemetry rides the same program as tiny replicated
            # outputs (fp32 squared sums; host takes the sqrt — fp64 host
            # norms and these agree to fp32 precision). param norm is over
            # the PRE-update parameters, state norms over the post-update
            # state — exactly what the host oracle's norm_telemetry sees
            # when apply_average calls it (strategy/base.py), keeping the
            # KPI meaning identical across the two optimizer paths
            sq = {
                "pseudo_grad": sum(jnp.sum(jnp.square(g)) for g in grads),
                "param": sum(jnp.sum(jnp.square(p)) for p in params),
            }
            for key, tensors in new_state.items():
                sq[key] = sum(jnp.sum(jnp.square(m)) for m in tensors)
            return new_params, new_state, n_total, sq

        return jax.jit(program)

    def _build_sharded_program(self, n_leaves: int) -> Callable:
        """The ZeRO-1 fused round (ISSUE 14): ONE shard_map'd program in
        which the weighted average's reduce-scatter output STAYS on each
        rank's chunk — pseudo-gradient, update rule, q8 clamp and norm
        telemetry all run sharded — and only the n_total/norm scalars leave
        replicated. Params are NOT gathered here: the post-update ICI
        all-gather runs on demand in :meth:`params_host` (the update leg),
        so between rounds every server-state tensor occupies 1/replica of a
        rank's HBM. Flat positional calling convention (shard_map in_specs
        are per-argument): ``(ns, *stacked, *param_shards, *state_shards,
        lr, b1t, b2t)``."""
        mesh = self.mesh
        rule, hyper = self.rule, dict(self.hyper)
        state_keys = self.state_keys
        n_state = len(state_keys)
        clamp_rows = (
            frozenset(self.nonneg_rows) if self.quantization == "q8" else frozenset()
        )
        reduce_to_shard = _make_reduce_to_shard(mesh, self.quantization, self.block)
        has_replica = self._has_replica
        shard_spec = P(REPLICA_AXIS) if has_replica else P()

        def local(*args):
            ns = args[0]
            stacked = args[1 : 1 + n_leaves]
            params = list(args[1 + n_leaves : 1 + 2 * n_leaves])
            state_flat = args[1 + 2 * n_leaves : 1 + (2 + n_state) * n_leaves]
            lr, b1t, b2t = args[-3:]
            _check_one_row(ns.shape)
            n_total = jax.lax.psum(jnp.sum(ns.astype(jnp.float32)), CLIENT_AXIS)
            w = ns[0].astype(jnp.float32) / n_total
            # the reduce-scatter output IS the rank's share of the average:
            # no all-gather before the update (the tentpole move)
            avg = [
                reduce_to_shard(leaf[0].astype(jnp.float32) * w)
                for leaf in stacked
            ]
            grads = [x - a for x, a in zip(params, avg)]
            state = {
                key: list(state_flat[j * n_leaves : (j + 1) * n_leaves])
                for j, key in enumerate(state_keys)
            }
            new_params, new_state = device_server_update(
                rule, params, grads, state, lr, b1t, b2t, **hyper
            )
            if clamp_rows:
                # restore the second-moment invariant the q8 noise breaks
                # (see __init__); padding stays exactly 0 under max(·, 0)
                new_params = [
                    jnp.maximum(p, 0.0) if i in clamp_rows else p
                    for i, p in enumerate(new_params)
                ]

            def _sq(tensors):
                # per-shard partial squared sums; the ICI psum reassembles
                # the global value (padding contributes exact zeros)
                s = sum(jnp.sum(jnp.square(t)) for t in tensors)
                return jax.lax.psum(s, REPLICA_AXIS) if has_replica else s

            sq = [_sq(grads), _sq(params)]
            for key in state_keys:
                sq.append(_sq(new_state[key]))
            out = list(new_params)
            for key in state_keys:
                out.extend(new_state[key])
            return tuple(out) + (n_total,) + tuple(sq)

        in_specs = (
            (P(CLIENT_AXIS),)
            + tuple(P(CLIENT_AXIS) for _ in range(n_leaves))
            + tuple(shard_spec for _ in range((1 + n_state) * n_leaves))
            + (P(), P(), P())
        )
        out_specs = tuple(
            shard_spec for _ in range((1 + n_state) * n_leaves)
        ) + tuple(P() for _ in range(3 + n_state))
        mapped = _full_shard_map(local, mesh, in_specs=in_specs, out_specs=out_specs)
        return jax.jit(mapped)

    def current_epoch(self) -> int:
        """Abandon-epoch token for ``run_round(epoch=...)``. Capture it on
        the CALLER thread before dispatching the stage worker: if the
        worker read the epoch itself, an :meth:`abandon` issued while the
        worker was still ramping up would be missed (the worker would see
        the post-bump value and its commit would pass the guard)."""
        with self._commit_lock:
            return self._epoch

    def run_round(
        self, stacked_flat: Sequence[jax.Array], n_samples: jax.Array,
        lr: float, epoch: int | None = None,
    ) -> dict[str, float]:
        """One fused server round over client-axis-sharded stacked rows.
        Updates the device-resident params/state in place and returns the
        round metrics (the same vocabulary as the host
        ``Strategy.apply_average``). Blocks until the program finishes (the
        scalar fetches below synchronize). ``epoch``: abandon-epoch token
        from :meth:`current_epoch` when running on a deadline-abandonable
        worker; defaults to the current epoch (inline callers)."""
        n_leaves = len(self._sizes)
        if len(stacked_flat) != n_leaves:
            raise ValueError(
                f"stacked payload has {len(stacked_flat)} arrays, plane holds "
                f"{n_leaves} (momenta mismatch? the server extends "
                "initial params with zero momenta when aggregate_momenta is on)"
            )
        if self._program is None:
            self._program = (
                self._build_sharded_program(n_leaves)
                if self.sharded else self._build_program(n_leaves)
            )
        if epoch is None:
            epoch = self.current_epoch()
        t_next = self.t + 1 if self.adaptive else self.t
        if self.adaptive:
            b1t = 1.0 - self.hyper["beta_1"] ** t_next
            b2t = 1.0 - self.hyper["beta_2"] ** t_next
        else:
            b1t = b2t = 1.0
        if self.sharded:
            n_state = len(self.state_keys)
            state_flat = tuple(
                t for key in self.state_keys for t in self.state[key]
            )
            out = self._program(
                n_samples, *stacked_flat, *self.params, *state_flat,
                jnp.float32(lr), jnp.float32(b1t), jnp.float32(b2t),
            )
            new_params = out[:n_leaves]
            new_state = {
                key: list(out[(1 + j) * n_leaves : (2 + j) * n_leaves])
                for j, key in enumerate(self.state_keys)
            }
            n_total = out[(1 + n_state) * n_leaves]
            sq_flat = out[(1 + n_state) * n_leaves + 1 :]
            sq = {"pseudo_grad": sq_flat[0], "param": sq_flat[1]}
            for j, key in enumerate(self.state_keys):
                sq[key] = sq_flat[2 + j]
        else:
            state_in = {k: tuple(v) for k, v in self.state.items()}
            new_params, new_state, n_total, sq = self._program(
                n_samples,
                tuple(stacked_flat),
                tuple(self.params),
                state_in,
                jnp.float32(lr),
                jnp.float32(b1t),
                jnp.float32(b2t),
            )
        from photon_tpu.utils.profiling import (
            EFFECTIVE_LR,
            N_CLIENTS,
            N_SAMPLES,
            PARAM_NORM,
            PSEUDO_GRAD_NORM,
        )

        metrics = {
            N_CLIENTS: float(self.n_clients),
            N_SAMPLES: float(np.asarray(n_total)),
            EFFECTIVE_LR: float(lr),
            PSEUDO_GRAD_NORM: float(np.sqrt(np.asarray(sq["pseudo_grad"]))),
            PARAM_NORM: float(np.sqrt(np.asarray(sq["param"]))),
        }
        for key in self.state_keys:
            metrics[f"server/{key}_norm"] = float(np.sqrt(np.asarray(sq[key])))
        # the scalar fetches above synchronized, so the program is known to
        # have completed — only now commit the round. A program that fails
        # (dispatch or at the fetch) leaves params/state/t at the previous
        # round, keeping bias correction honest across a retry/checkpoint.
        # An ABANDONED run (the caller hit a stage deadline and moved on —
        # :meth:`abandon`) skips the commit entirely: the round it belonged
        # to already completed another way.
        with self._commit_lock:
            if epoch == self._epoch:
                self.params = list(new_params)
                self.state = {k: list(v) for k, v in new_state.items()}
                self.t = t_next
        return metrics

    def abandon(self) -> None:
        """Disown any in-flight :meth:`run_round` (the caller's stage
        deadline fired and the round will complete another way): when the
        abandoned worker eventually finishes, its commit is skipped. Blocks
        until any commit already past its epoch check has finished its
        writes, so a subsequent :meth:`reseed_from` can never interleave
        with a stale commit."""
        with self._commit_lock:
            self._epoch += 1

    def snapshot(self) -> tuple:
        """Commit-state snapshot (cheap reference copies — device arrays
        are immutable) taken before a collective attempt. A failed attempt
        may have ALREADY committed its fused run (the exchange landed, then
        the update stage missed its deadline): :meth:`restore` rolls the
        plane back so the retry re-applies the round ONCE, not on top of
        the half-finished attempt's step."""
        with self._commit_lock:
            return (list(self.params),
                    {k: list(v) for k, v in self.state.items()}, self.t)

    def restore(self, snap: tuple) -> None:
        """Roll back to a :meth:`snapshot` (pair with :meth:`abandon`
        first, so a straggling worker can't re-commit over the rollback)."""
        params, state, t = snap
        with self._commit_lock:
            self.params = list(params)
            self.state = {k: list(v) for k, v in state.items()}
            self.t = t

    # -- host bridges ------------------------------------------------------
    def _gather_host(self, arrays: list) -> list[np.ndarray]:
        """Sharded padded-flat device arrays → full host leaves: the cached
        ICI all-gather program reassembles, then the padding drops and the
        original shapes return. Value-preserving by construction — this is
        what makes checkpoints bit-exact across a resharding."""
        if not arrays:
            return []
        if self._has_replica:
            arrays = _gather_program(self.mesh, len(arrays))(*arrays)
        return [
            np.asarray(a)[: self._sizes[i]].reshape(self._shapes[i])
            for i, a in enumerate(arrays)
        ]

    def params_host(self) -> list[np.ndarray]:
        if not self.sharded:
            return [np.asarray(p) for p in self.params]
        # THE all-gather of the round (ISSUE 14): updated params reassemble
        # here, after the update — timed for server/opt_allgather_time
        t0 = time.perf_counter()
        params = self.params
        out = self._gather_host(list(params))
        self.last_allgather_s = time.perf_counter() - t0
        return out

    def state_host(self) -> dict[str, list[np.ndarray]]:
        if not self.sharded:
            return {k: [np.asarray(a) for a in v] for k, v in self.state.items()}
        return {k: self._gather_host(list(v)) for k, v in self.state.items()}

    def server_state_bytes_per_rank(self) -> int:
        """Persistent server-state bytes ONE ICI rank holds between rounds
        (params + every optimizer-state tensor, fp32): each leaf counts its
        per-rank chunk on the sharded plane, its full size replicated.
        ``tests/test_collective_agg.py`` pins sharded ≤ (1/replica + 0.05)
        × replicated."""
        per_leaf = self._chunks if self.sharded else self._sizes
        return 4 * sum(per_leaf) * (1 + len(self.state_keys))

    def shard_fraction(self) -> float:
        """Per-rank fraction of the full server state this plane keeps
        resident (``server/opt_shard_frac``): 1.0 replicated, ≈1/replica
        sharded (chunk padding makes it marginally larger)."""
        return sum(self._chunks if self.sharded else self._sizes) / max(
            sum(self._sizes), 1
        )

    def sync_strategy(self, strategy: Any) -> None:
        """Mirror the device-resident round results back into the host
        strategy, so ``Strategy.state_for_checkpoint`` (and the broadcast
        path reading ``current_parameters``) see exactly what the device
        plane computed."""
        strategy.current_parameters = self.params_host()
        strategy.restore_optimizer_state(self.state_host(), t=self.t)

    def reseed_from(self, strategy: Any) -> None:
        """Inverse of :meth:`sync_strategy`: re-device_put params/state from
        the host strategy after a round ran OFF the plane (gang
        reconfiguration over a survivors cohort, or the host-fallback fold —
        ISSUE 8). The cached fused program is kept — rebuilding the plane
        would recompile it, which the retrace discipline forbids — and the
        adaptive ``_t`` follows the host strategy, which incremented it when
        it applied the off-plane update."""
        if strategy.current_parameters is None:
            raise RuntimeError("strategy not initialized with parameters")
        with self._commit_lock:
            self._seed_from_host(strategy)
            self.t = int(getattr(strategy, "_t", self.t))

    def modeled_round_bytes(self) -> int:
        """Modeled cross-slice DCN bytes for one round over this plane's
        payload structure (see :func:`modeled_cross_slice_bytes`)."""
        return modeled_cross_slice_bytes(
            list(self._sizes),
            self.n_clients,
            replica=mesh_replica(self.mesh),
            quantization=self.quantization,
            block=self.block,
        )
