"""Server-side optimizers on the pseudo-gradient, layer by layer.

Update rules match the reference strategies (``g`` is the pseudo-gradient
``x - avg``). Each class writes its rule once, for one chunk of one array
(``_rule``: numpy calls with ``out=``, in the order of the formula, so that
the float32 result is the formula's to the bit);
``Strategy.server_update`` runs it over every chunk of the model:

- FedAvgEff   (``fedavg_eff.py:291-330``):   x ← x − η·g
- FedNesterov (``fednestorov.py:323-331``):  m ← μm + g;  x ← x − η·(g + μm)
- FedMom      (``fedmom.py``):               m ← μm + g;  x ← x − η·m
- FedAdam     (``fedadam.py:291-318``):      bias-corrected Adam on g
- FedYogi     (``fedyogi.py:299-320``):      Yogi second-moment variant

DELIBERATE DIVERGENCE — FedAdam/FedYogi update sign. The reference computes
the pseudo-gradient as ``g = x − avg`` (``fedadam.py:293``) and then applies
``x ← x + η·m̂/(√v̂+τ)`` (``fedadam.py:307-317``, same in
``fedyogi.py:313-322``) — a step in the *+g* direction, i.e. AWAY from the
client average. Every other strategy in the reference descends: FedAvgEff
with η=1 lands exactly on the average via ``x − g``, and Adaptive Federated
Optimization (Reddi et al. 2021) defines FedAdam with ``Δ = avg − x`` and
``x ← x + η·m̂/(√v̂+τ)``, which equals ``x − η·…`` under our ``g = x − avg``
convention. We therefore SUBTRACT (``x − η·m̂/(√v̂+τ)``): consistent with the
published algorithm and with descent; the reference's ``+`` on its ``x − avg``
pseudo-gradient is judged a sign bug, not behavior to reproduce. Golden tests
pin our sign (``tests/test_strategy.py::test_fedadam_first_step_golden``,
``test_adaptive_descends_toward_client_average``).

A second, minor divergence: the reference bias-corrects with ``server_round``
(``fedadam.py:308,312``) which is wrong after a warm start from a non-zero
round with fresh momenta; we keep an internal ``_t`` counter that is
checkpointed/restored with the strategy state.
"""

from __future__ import annotations

from typing import Any

import numpy as np

from photon_tpu.strategy.base import Strategy


class FedAvgEff(Strategy):
    """Plain server SGD on the pseudo-gradient; η=1, μ=0 == exact FedAvg."""

    name = "fedavg"

    def _rule(self, x, g, state, lr, new_x, new_state, tmp):
        t, _ = tmp
        np.multiply(g, lr, out=t)
        np.subtract(x, t, out=new_x)


class FedNesterov(Strategy):
    """Nesterov-momentum server optimizer (the reference's default federated
    strategy: NESTOROV lr=1.0 μ=0.0, ``conf/base.yaml:63-66``)."""

    name = "nesterov"
    state_keys = ("momentum",)

    def _rule(self, x, g, state, lr, new_x, new_state, tmp):
        (m,), (m_new,), (t, _) = state, new_state, tmp
        np.multiply(m, self.momentum, out=m_new)
        m_new += g
        np.multiply(m_new, self.momentum, out=t)
        np.add(g, t, out=t)
        t *= lr
        np.subtract(x, t, out=new_x)


class FedMom(Strategy):
    """Heavy-ball momentum server optimizer."""

    name = "fedmom"
    state_keys = ("momentum",)

    def _rule(self, x, g, state, lr, new_x, new_state, tmp):
        (m,), (m_new,), (t, _) = state, new_state, tmp
        np.multiply(m, self.momentum, out=m_new)
        m_new += g
        np.multiply(m_new, lr, out=t)
        np.subtract(x, t, out=new_x)


class _AdaptiveBase(Strategy):
    state_keys = ("momentum_1", "momentum_2")

    def __init__(
        self,
        server_learning_rate: float = 1.0,
        server_beta_1: float = 0.9,
        server_beta_2: float = 0.99,
        server_tau: float = 1.0e-9,
        **kw: Any,
    ) -> None:
        super().__init__(server_learning_rate=server_learning_rate, **kw)
        self.beta_1 = server_beta_1
        self.beta_2 = server_beta_2
        self.tau = server_tau
        self._t = 0

    def _second_moment(self, v, g, v_new, tmp) -> None:
        """Write the new second moment of one chunk into ``v_new``."""
        raise NotImplementedError

    def server_update(self, pseudo_grad, lr):
        self._t += 1
        return super().server_update(pseudo_grad, lr)

    def _rule(self, x, g, state, lr, new_x, new_state, tmp):
        (m, v), (m_new, v_new), (t, u) = state, new_state, tmp
        np.multiply(m, self.beta_1, out=m_new)
        np.multiply(g, 1.0 - self.beta_1, out=t)
        m_new += t
        self._second_moment(v, g, v_new, tmp)
        np.divide(m_new, 1.0 - self.beta_1**self._t, out=t)  # bias-corrected
        np.divide(v_new, 1.0 - self.beta_2**self._t, out=u)
        np.sqrt(u, out=u)
        u += self.tau
        t *= lr
        t /= u
        np.subtract(x, t, out=new_x)

    # step counter must survive resume (bias correction continuity; the
    # reference persists it via strategy state_keys round indexing)
    def state_for_checkpoint(self):
        d = super().state_for_checkpoint()
        d["_t"] = [np.asarray([self._t], np.int64)]
        return d

    def initialize(self, parameters, state=None):
        state = dict(state or {})
        t = state.pop("_t", None)
        super().initialize(parameters, state)
        if t is not None:
            self._t = int(np.asarray(t[0]).ravel()[0])

    def restore_optimizer_state(self, state, t=None):
        # the device plane advances its own step counter; adopting its
        # momenta without the matching _t would reset bias correction on
        # the next checkpoint → resume cycle
        state = dict(state)
        state.pop("_t", None)
        super().restore_optimizer_state(state)
        if t is not None:
            self._t = int(t)


class FedAdam(_AdaptiveBase):
    name = "fedadam"

    def _second_moment(self, v, g, v_new, tmp):
        t, _ = tmp
        np.multiply(v, self.beta_2, out=v_new)
        np.square(g, out=t)
        t *= 1.0 - self.beta_2
        v_new += t


class FedYogi(_AdaptiveBase):
    name = "fedyogi"

    def _second_moment(self, v, g, v_new, tmp):
        g2, s = tmp
        np.square(g, out=g2)
        np.subtract(v, g2, out=s)
        np.sign(s, out=s)
        g2 *= 1.0 - self.beta_2
        g2 *= s
        np.subtract(v, g2, out=v_new)
