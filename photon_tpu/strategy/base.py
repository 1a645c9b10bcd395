"""Strategy base: pseudo-gradient server optimization over flat ndarray lists.

Reference architecture (``photon/strategy/fedavg_eff.py`` etc.): the server
holds the global parameters; each round it averages client parameters
(streaming, sample-weighted), forms the pseudo-gradient

    g_i = x_i - avg_i        (per layer)

and applies a server-side optimizer update layer by layer. Subclasses
implement :meth:`server_update`. ``state_keys`` declare which optimizer state
tensors are checkpointed alongside the parameters (reference:
``fedadam.py:197-201``).
"""

from __future__ import annotations

import dataclasses
import math
from collections.abc import Sequence
from typing import Any, Iterable

import numpy as np

from photon_tpu import telemetry as _telemetry  # __init__ has a `telemetry` flag
from photon_tpu.strategy.aggregation import (
    aggregate_inplace,
    chunk_buffers,
    diff_sumsq,
    flat_views,
    map_chunks,
    sumsq,
    weighted_average_metrics,
)
from photon_tpu.utils.hostpool import HostPool
from photon_tpu.utils.profiling import (
    AGG_DECODE_TIME,
    AGG_FOLD_TIME,
    EFFECTIVE_LR,
    EVAL_LOSS,
    N_CLIENTS,
    N_SAMPLES,
    PARAM_NORM,
    PSEUDO_GRAD_NORM,
    SERVER_UPDATE_SPAN,
)


@dataclasses.dataclass
class ClientResult:
    """One client's round output (the FitRes analog).

    ``arrays`` is either the flat ndarray list or — when the wire codec is
    on — a still-compressed
    :class:`photon_tpu.compression.CompressedPayload`, dequantized lazily
    inside the streaming aggregation (one client resident at a time)."""

    cid: int
    arrays: list[np.ndarray]  # or a CompressedPayload (see above)
    n_samples: int
    metrics: dict[str, float] = dataclasses.field(default_factory=dict)


def l2_norm(arrays: Iterable[np.ndarray], pool=None) -> float:
    return math.sqrt(sumsq(arrays, pool))


class PseudoGrad(Sequence):
    """The pseudo-gradient ``x - avg`` as :meth:`Strategy.apply_average`
    hands it to :meth:`Strategy.server_update`: a sequence of arrays that
    holds only its two operands. The rules and the norms take the
    difference chunk by chunk, so a model-sized array of it never exists;
    indexing gives one array's difference whole, for code that wants plain
    arrays."""

    def __init__(self, params: list[np.ndarray], avg: list[np.ndarray]) -> None:
        self.params = params
        self.avg = avg

    def __len__(self) -> int:
        return len(self.params)

    def __getitem__(self, i: int) -> np.ndarray:
        return self.params[i] - self.avg[i]


class Strategy:
    """Base server strategy.

    ``current_parameters`` (and any momenta) are injected after init/resume
    (reference: ``initialize_strategy``, ``photon/strategy/utils.py:13-54``).
    """

    name = "base"
    #: names of per-layer state lists checkpointed with the params
    state_keys: tuple[str, ...] = ()

    def __init__(
        self,
        server_learning_rate: float = 1.0,
        server_momentum: float = 0.0,
        client_count_scaling: str = "none",
        telemetry: bool = True,
        **_: Any,
    ) -> None:
        self.eta = server_learning_rate
        self.momentum = server_momentum
        self.client_count_scaling = client_count_scaling
        self.telemetry = telemetry
        self.current_parameters: list[np.ndarray] | None = None
        self.state: dict[str, list[np.ndarray]] = {}
        self.server_round = 0
        #: decoder for compressed ClientResult payloads (wired by ServerApp
        #: when the transport carries a wire codec); None = raw arrays only
        self.payload_decoder = None
        #: shared host thread pool (wired by ServerApp from
        #: ``photon.host_threads``); None = fully serial aggregation
        self.host_pool = None

    # ------------------------------------------------------------------
    def initialize(self, parameters: list[np.ndarray], state: dict[str, list[np.ndarray]] | None = None) -> None:
        self.current_parameters = [np.asarray(p, np.float32) for p in parameters]
        if state:
            self.state = {k: [np.asarray(a, np.float32) for a in v] for k, v in state.items()}
        for key in self.state_keys:
            if key not in self.state:
                self.state[key] = [np.zeros_like(p) for p in self.current_parameters]

    def effective_lr(self, n_clients: int) -> float:
        """lr scaling with sampled-client count (reference:
        ``fedavg_eff.py:291-330`` linear/sqrt options)."""
        if self.client_count_scaling == "linear":
            return self.eta * n_clients
        if self.client_count_scaling == "sqrt":
            return self.eta * math.sqrt(n_clients)
        return self.eta

    # ------------------------------------------------------------------
    def aggregate_fit(
        self, server_round: int, results: Iterable[ClientResult]
    ) -> tuple[list[np.ndarray], dict[str, float]]:
        """Streaming average → pseudo-gradient → server optimizer.

        ``results`` may be a generator; client tensors are folded into the
        running average one at a time (reference: ``handle_fit_replies`` lazy
        pipeline, ``server/fit_utils.py:92-217``).
        """
        if self.current_parameters is None:
            raise RuntimeError("strategy not initialized with parameters")
        self.server_round = server_round

        seen: list[tuple[int, dict[str, float]]] = []

        def stream():
            for r in results:
                seen.append((r.n_samples, r.metrics))
                yield r.arrays, r.n_samples

        timings: dict[str, float] = {}
        avg, n_total = aggregate_inplace(
            stream(),
            decode=self.payload_decoder,
            pool=self.host_pool,
            timings=timings,
        )
        metrics = self.apply_average(server_round, avg, n_total, len(seen))
        # host-plane KPI decomposition (utils/profiling.py): fetch+decode vs
        # fold seconds of the streaming aggregation (summed across workers
        # on the pipelined path, so they can exceed wall-clock)
        metrics[AGG_DECODE_TIME] = timings.get("decode_s", 0.0)
        metrics[AGG_FOLD_TIME] = timings.get("fold_s", 0.0)
        metrics.update(weighted_average_metrics(seen))
        return self.current_parameters, metrics

    def apply_average(
        self,
        server_round: int,
        avg: list[np.ndarray],
        n_total: int,
        n_clients: int,
    ) -> dict[str, float]:
        """Post-average half of the round: pseudo-gradient → server optimizer
        → telemetry. Shared by the host streaming path (:meth:`aggregate_fit`)
        and the on-device collective path
        (``photon_tpu/federation/collective_round.py``), where the weighted
        average arrives from a DCN/ICI psum instead of ``aggregate_inplace``
        — every controller applies this identical deterministic update to its
        strategy replica.

        ``avg`` is only read (callers pass read-only views of device arrays).
        Nothing reachable before the call is written: the new parameters and
        the new optimizer state are fresh arrays that ``current_parameters``
        and ``state`` are rebound to, so a checkpoint writer or a pinned
        broadcast that still holds the old ones sees them unchanged. Those
        are the call's only model-sized allocations: pseudo-gradient, rule
        and norms run chunk by chunk over ``host_pool``."""
        if self.current_parameters is None:
            raise RuntimeError("strategy not initialized with parameters")
        if len(avg) != len(self.current_parameters):
            # zip() would silently truncate — e.g. a [params|m1|m2] momenta
            # payload averaged against momenta-less current_parameters
            raise ValueError(
                f"averaged payload has {len(avg)} arrays, strategy holds "
                f"{len(self.current_parameters)} (momenta mismatch? the "
                "server extends initial params with zero momenta when "
                "aggregate_momenta is on)"
            )
        self.server_round = server_round
        with _telemetry.span(SERVER_UPDATE_SPAN, round=server_round,
                             threads=self.host_pool.threads if self.host_pool else 1):
            pseudo_grad = PseudoGrad(self.current_parameters, avg)
            lr = self.effective_lr(n_clients)
            new_params = self.server_update(pseudo_grad, lr)

            metrics: dict[str, float] = {
                N_CLIENTS: float(n_clients),
                N_SAMPLES: float(n_total),
                EFFECTIVE_LR: lr,
            }
            if self.telemetry:
                metrics.update(self.norm_telemetry(pseudo_grad))
            self.current_parameters = new_params
        return metrics

    def aggregate_evaluate(
        self, server_round: int, results: Iterable[tuple[int, float, dict[str, float]]]
    ) -> tuple[float, dict[str, float]]:
        """Sample-weighted eval-loss aggregation (reference:
        ``evaluate_utils.py:33-158``)."""
        results = list(results)
        from photon_tpu.strategy.aggregation import weighted_loss_avg

        loss = weighted_loss_avg([(n, l) for n, l, _ in results])
        metrics = weighted_average_metrics([(n, m) for n, l, m in results])
        metrics[EVAL_LOSS] = loss
        return loss, metrics

    # ------------------------------------------------------------------
    def server_update(self, pseudo_grad: Sequence[np.ndarray], lr: float) -> list[np.ndarray]:
        """The rule over the whole model: new parameters from
        ``current_parameters``, the pseudo-gradient (a :class:`PseudoGrad` or
        plain arrays) and the state named by ``state_keys``, which is rebound
        to its new value. One pass of :meth:`_rule` over every chunk of every
        array, spread over ``host_pool``; the chunks are disjoint and each
        is written once, so the result is the same on any number of
        threads."""
        assert self.current_parameters is not None
        params = self.current_parameters
        if len(pseudo_grad) != len(params):
            raise ValueError(f"{len(pseudo_grad)} pseudo-gradient arrays, {len(params)} parameters")
        pool = self.host_pool or HostPool(1)
        lazy = isinstance(pseudo_grad, PseudoGrad)
        x = flat_views(params)
        g = flat_views(pseudo_grad.avg if lazy else pseudo_grad)
        old = [flat_views(self.state[k]) for k in self.state_keys]
        # C order, whatever the old array's: reshape(-1) below must be a view
        new_params = [np.empty(p.shape, p.dtype) for p in params]
        new_state = [[np.empty(a.shape, a.dtype) for a in self.state[k]]
                     for k in self.state_keys]
        new_x = [a.reshape(-1) for a in new_params]
        new = [[a.reshape(-1) for a in tensors] for tensors in new_state]

        def one(i: int, sl: slice) -> None:
            xc, gc = x[i][sl], g[i][sl]
            diff, *tmp = (b[:xc.size] for b in chunk_buffers(pool, xc.dtype, 3))
            self._rule(
                xc, np.subtract(xc, gc, out=diff) if lazy else gc,
                [s[i][sl] for s in old], lr,
                new_x[i][sl], [s[i][sl] for s in new], tmp,
            )

        map_chunks(one, [f.size for f in x], pool)
        self.state.update(zip(self.state_keys, new_state))
        return new_params

    def _rule(self, x, g, state, lr, new_x, new_state, tmp) -> None:
        """The rule on one chunk: from parameters ``x``, pseudo-gradient
        ``g`` and the ``state`` chunks (in ``state_keys`` order), all only
        read, write ``new_x`` and ``new_state``. ``tmp`` is two scratch
        chunks. Float32 operations in the order the rule is written in the
        module docstring of ``optimizers``: the goldens and the device
        plane's port are held to the bits."""
        raise NotImplementedError

    def norm_telemetry(self, pseudo_grad: PseudoGrad) -> dict[str, float]:
        """Global L2 norms of pseudo-grad / params / momenta (reference
        per-layer + global norms, ``fedadam.py:333-381``; per-layer norms are
        computed on demand by callers to keep round metrics compact).
        Pseudo-gradient and parameters (those the pseudo-gradient was taken
        from) share one pass."""
        g2, x2 = diff_sumsq(pseudo_grad.params, pseudo_grad.avg, self.host_pool)
        out = {PSEUDO_GRAD_NORM: math.sqrt(g2), PARAM_NORM: math.sqrt(x2)}
        for key, tensors in self.state.items():
            out[f"server/{key}_norm"] = l2_norm(tensors, self.host_pool)
        return out

    def per_layer_norms(self, names: list[str], arrays: list[np.ndarray], prefix: str) -> dict[str, float]:
        return {
            f"{prefix}/{n}": float(np.linalg.norm(a.astype(np.float64)))
            for n, a in zip(names, arrays)
        }

    # checkpointing --------------------------------------------------------
    def state_for_checkpoint(self) -> dict[str, list[np.ndarray]]:
        return {k: self.state[k] for k in self.state_keys if k in self.state}

    # per-version rollback (ISSUE 18) --------------------------------------
    def snapshot(self) -> tuple[list[np.ndarray], dict[str, list[np.ndarray]], int]:
        """Deep copy of (params, optimizer state, adaptive step counter) —
        the async runner's per-version rollback point: a fold that raises
        mid-update must leave the strategy exactly at the pre-fold version,
        never half-stepped. Same shape the device plane's own
        ``snapshot()`` uses, so host and device mirrors roll back together."""
        if self.current_parameters is None:
            raise RuntimeError("strategy not initialized with parameters")
        return (
            [p.copy() for p in self.current_parameters],
            {k: [a.copy() for a in v] for k, v in self.state.items()},
            int(getattr(self, "_t", 0)),
        )

    def restore(self, snap: tuple[list[np.ndarray], dict[str, list[np.ndarray]], int]) -> None:
        params, state, t = snap
        self.current_parameters = [p.copy() for p in params]
        self.restore_optimizer_state(
            {k: [a.copy() for a in v] for k, v in state.items()}, t=t
        )

    def restore_optimizer_state(
        self, state: dict[str, list[np.ndarray]], t: int | None = None
    ) -> None:
        """Adopt optimizer state computed elsewhere — the device aggregation
        plane (``parallel/collective_agg.py``) syncs its device-resident
        momenta back through here so :meth:`state_for_checkpoint` serializes
        exactly what the fused on-device round produced. ``t`` is the
        adaptive strategies' step counter; the base/momentum rules ignore
        it (see the override in ``optimizers._AdaptiveBase``)."""
        self.state = {
            k: [np.asarray(a, np.float32) for a in v] for k, v in state.items()
        }
