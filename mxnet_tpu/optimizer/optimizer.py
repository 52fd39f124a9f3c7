"""Optimizer library.

Reference: ``python/mxnet/optimizer/optimizer.py:51-1904`` — an ``Optimizer``
base with a string registry and 18 concrete optimizers, stateful per-index
update counts, lr/wd multipliers, rescale_grad and gradient clipping; the
actual math lives in fused CUDA ops (``src/operator/optimizer_op.cc:320-656``).

TPU-native re-design: every optimizer's math is a *pure function*
``(weight, grad, state, lr, wd) -> (new_weight, new_state)`` on jax arrays —
XLA fuses the elementwise chain into one kernel (the analog of the reference's
fused sgd_mom_update etc.), and the same pure core is reused unchanged inside
jit-compiled data-parallel training steps (see mxnet_tpu.parallel).  The
``Optimizer``/``Updater`` classes keep the reference's stateful API for
script-level parity.
"""
from __future__ import annotations

import math
import pickle

import jax.numpy as jnp
import numpy as _np

from ..base import dtype_np
from ..ndarray.ndarray import NDArray, _wrap, zeros as nd_zeros

__all__ = ["Optimizer", "create", "register", "Updater", "get_updater",
           "SGD", "Signum", "SignSGD", "FTML", "LARS", "LBSGD", "DCASGD", "NAG",
           "SGLD", "ccSGD", "Adam", "AdaGrad", "RMSProp", "AdaDelta", "Ftrl",
           "Adamax", "Nadam", "Test", "GroupAdaGrad"]


def _clip(x, bound):
    if bound is None or bound <= 0:
        return x
    return jnp.clip(x, -bound, bound)


class Optimizer:
    """Base optimizer (reference: optimizer.py:51).

    State is per-parameter-index, created by ``create_state``; ``update``
    applies one step.  All math on jax arrays via the subclass's pure
    ``step(weight, grad, state, lr, wd, t)``.
    """

    opt_registry = {}

    # ``step`` is a pure function of (weight, grad, state, lr, wd, t) and
    # may be traced into a fused jit train step with lr/wd/t fed as device
    # arrays (Module's fused path, SPMDTrainer).  Subclasses whose step
    # reads or mutates Python-side per-step state that is NOT in ``state``
    # (so it would constant-fold at trace time or drift across traced
    # calls) must set this False to keep the eager per-parameter path.
    jit_safe = True

    @staticmethod
    def register(klass):
        name = klass.__name__.lower()
        Optimizer.opt_registry[name] = klass
        return klass

    @staticmethod
    def create_optimizer(name, **kwargs):
        if name.lower() in Optimizer.opt_registry:
            return Optimizer.opt_registry[name.lower()](**kwargs)
        raise ValueError("Cannot find optimizer %s" % name)

    def __init__(self, rescale_grad=1.0, param_idx2name=None, wd=0.0,
                 clip_gradient=None, learning_rate=0.01, lr_scheduler=None,
                 sym=None, begin_num_update=0, multi_precision=False,
                 param_dict=None):
        self.rescale_grad = rescale_grad
        self.lr = learning_rate
        self.lr_scheduler = lr_scheduler
        if lr_scheduler is not None:
            self.lr_scheduler.base_lr = learning_rate
        self.wd = wd
        self.begin_num_update = begin_num_update
        self.num_update = begin_num_update
        self._all_index_update_counts = {0: {}}
        self._index_update_count = self._all_index_update_counts[0]
        self.clip_gradient = clip_gradient
        self.multi_precision = multi_precision
        self.aggregate_num = 0
        if param_idx2name is None:
            param_idx2name = {}
        assert isinstance(param_idx2name, dict), \
            "param_idx2name should be a dict of param indexes to names."
        self.idx2name = param_idx2name.copy()
        self.sym_info = ()
        self.param_dict = param_dict if param_dict else {}
        self.set_lr_mult({})
        self.set_wd_mult({})

    # ------------------------------------------------------------- lr & wd
    def set_learning_rate(self, lr):
        if self.lr_scheduler is not None:
            raise UserWarning("LRScheduler of the optimizer has already been "
                              "defined. Note that set_learning_rate can mutate "
                              "the value of the learning rate of the optimizer "
                              "only when the LRScheduler of the optimizer is "
                              "undefined.")
        self.lr = lr

    @property
    def learning_rate(self):
        if self.lr_scheduler is not None:
            return self.lr_scheduler(self.num_update)
        return self.lr

    def set_lr_mult(self, args_lr_mult):
        self.lr_mult = {}
        self.lr_mult.update(args_lr_mult)

    def set_wd_mult(self, args_wd_mult):
        self.wd_mult = {}
        for n in self.idx2name.values():
            is_weight = n.endswith("_weight")
            if not is_weight:
                self.wd_mult[n] = 0.0
        self.wd_mult.update(args_wd_mult)

    def _set_current_context(self, device_id):
        if device_id not in self._all_index_update_counts:
            self._all_index_update_counts[device_id] = {}
        self._index_update_count = self._all_index_update_counts[device_id]

    def _update_count(self, index):
        if not isinstance(index, (list, tuple)):
            index = [index]
        for idx in index:
            if idx not in self._index_update_count:
                self._index_update_count[idx] = self.begin_num_update
            self._index_update_count[idx] += 1
            self.num_update = max(self._index_update_count[idx], self.num_update)

    def _get_lrs(self, indices):
        if self.lr_scheduler is not None:
            lr = self.lr_scheduler(self.num_update)
        else:
            lr = self.lr
        lrs = [lr for _ in indices]
        for i, index in enumerate(indices):
            if index in self.param_dict:
                lrs[i] *= self.param_dict[index].lr_mult
            elif index in self.lr_mult:
                lrs[i] *= self.lr_mult[index]
            elif index in self.idx2name:
                lrs[i] *= self.lr_mult.get(self.idx2name[index], 1.0)
        return lrs

    def _get_lr(self, index):
        return self._get_lrs([index])[0]

    def _get_wds(self, indices):
        wds = [self.wd for _ in indices]
        for i, index in enumerate(indices):
            if index in self.param_dict:
                wds[i] *= self.param_dict[index].wd_mult
            elif index in self.wd_mult:
                wds[i] *= self.wd_mult[index]
            elif index in self.idx2name:
                wds[i] *= self.wd_mult.get(self.idx2name[index], 1.0)
        return wds

    def _get_wd(self, index):
        return self._get_wds([index])[0]

    # ------------------------------------------------------------ state API
    def create_state(self, index, weight):
        """Return optimizer state for one parameter (None | NDArray | tuple)."""
        return None

    def create_state_multi_precision(self, index, weight):
        """fp32 master copy for low-precision weights (reference: optimizer.py:284)."""
        if self.multi_precision and str(weight.dtype) in ("float16", "bfloat16"):
            master = _wrap(jnp.asarray(weight._data, jnp.float32))
            return (master, self.create_state(index, master))
        return self.create_state(index, weight)

    # ------------------------------------------------------------ update API
    def step(self, weight, grad, state, lr, wd, t):
        """Pure update: jax arrays in, (new_weight, new_state) out."""
        raise NotImplementedError

    def _preprocess_grad(self, grad):
        g = grad * self.rescale_grad
        return _clip(g, self.clip_gradient)

    def update(self, index, weight, grad, state):
        """One optimizer step for parameter `index` (mutates weight/state)."""
        if isinstance(index, (list, tuple)):
            for i, w, g, s in zip(index, weight, grad, state):
                self.update(i, w, g, s)
            return
        self._update_count(index)
        lr = self._get_lr(index)
        wd = self._get_wd(index)
        t = self._index_update_count[index]
        from ..ndarray.sparse import RowSparseNDArray
        if isinstance(grad, RowSparseNDArray) and \
                getattr(self, "lazy_update", False) and \
                hasattr(self, "step_rows"):
            # lazy sparse update (reference optimizer.py:524+): ONLY the
            # rows present in the gradient are touched — stale rows see no
            # weight decay and no momentum decay
            from .. import telemetry as _telemetry
            _telemetry.counter("optimizer.lazy_row_updates").inc()
            grad._refresh_sparse()
            rows = grad._indices
            vals = self._preprocess_grad(grad._values)
            new_w, new_state = self.step_rows(
                weight._data, rows, vals, _state_data(state), lr, wd, t)
            weight._set_data(jnp.asarray(new_w, dtype=weight._data.dtype))
            _state_write(state, new_state)
            return
        g = self._preprocess_grad(grad._data)
        new_w, new_state = self.step(weight._data, g, _state_data(state),
                                     lr, wd, t)
        weight._set_data(jnp.asarray(new_w, dtype=weight._data.dtype))
        _state_write(state, new_state)

    def update_multi_precision(self, index, weight, grad, state):
        use_mp = (self.multi_precision
                  and str(weight.dtype) in ("float16", "bfloat16"))
        if use_mp and isinstance(state, tuple) and len(state) == 2 \
                and isinstance(state[0], NDArray):
            master, real_state = state
            self._update_count(index)
            lr = self._get_lr(index)
            wd = self._get_wd(index)
            t = self._index_update_count[index]
            g = self._preprocess_grad(jnp.asarray(grad._data, jnp.float32))
            new_w, new_state = self.step(master._data, g,
                                         _state_data(real_state),
                                         lr, wd, t)
            master._set_data(new_w)
            weight._set_data(jnp.asarray(new_w, dtype=weight._data.dtype))
            _state_write(real_state, new_state)
        else:
            self.update(index, weight, grad, state)

    def __getstate__(self):
        ret = self.__dict__.copy()
        return ret

    def __setstate__(self, state):
        self.__dict__.update(state)


register = Optimizer.register
create = Optimizer.create_optimizer


def _state_data(state):
    """NDArray state tree → jax array tree."""
    if state is None:
        return None
    if isinstance(state, NDArray):
        return state._data
    if isinstance(state, (list, tuple)):
        return tuple(_state_data(s) for s in state)
    return state


def _state_write(state, new):
    """Write new jax values back into NDArray state tree in place."""
    if state is None:
        return
    if isinstance(state, NDArray):
        state._set_data(jnp.asarray(new, dtype=state._data.dtype))
        return
    if isinstance(state, (list, tuple)):
        for s, n in zip(state, new):
            _state_write(s, n)


def _zeros_like(weight, dtype=None):
    return _wrap(jnp.zeros(weight.shape, dtype_np(dtype) if dtype else weight._data.dtype))


# ---------------------------------------------------------------------------
# concrete optimizers
# ---------------------------------------------------------------------------

@register
class SGD(Optimizer):
    """SGD with momentum (reference: optimizer.py:524, fused kernels
    src/operator/optimizer_op.cc:320-656)::

        state = momentum * state + lr * (rescale_grad * grad + wd * weight)
        weight = weight - state

    ``lazy_update`` is accepted for sparse-API parity (dense path ignores it).
    """

    def __init__(self, momentum=0.0, lazy_update=True, **kwargs):
        super().__init__(**kwargs)
        self.momentum = momentum
        self.lazy_update = lazy_update

    def create_state(self, index, weight):
        if self.momentum != 0.0:
            return _zeros_like(weight)
        return None

    def step(self, weight, grad, state, lr, wd, t):
        g = grad + wd * weight
        if self.momentum == 0.0:
            return weight - lr * g, None
        mom = self.momentum * state + lr * g
        return weight - mom, mom

    def step_rows(self, weight, rows, grad_rows, state, lr, wd, t):
        """Lazy row_sparse step: touch ONLY `rows` (reference
        optimizer.py:524 sgd lazy_update via sgd_update(lazy_update=True))."""
        g = grad_rows + wd * weight[rows]
        if self.momentum == 0.0:
            return weight.at[rows].add(-lr * g), None
        mom_rows = self.momentum * state[rows] + lr * g
        return (weight.at[rows].add(-mom_rows),
                state.at[rows].set(mom_rows))


@register
class Signum(Optimizer):
    """Sign-of-momentum SGD (reference: optimizer.py:727)."""

    def __init__(self, learning_rate=0.01, momentum=0.9, wd_lh=0.0, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.momentum = momentum
        self.wd_lh = wd_lh

    def create_state(self, index, weight):
        if self.momentum != 0.0:
            return _zeros_like(weight)
        return None

    def step(self, weight, grad, state, lr, wd, t):
        if state is not None:
            mom = self.momentum * state - (1 - self.momentum) * (grad + wd * weight)
            w = (1 - lr * self.wd_lh) * weight + lr * jnp.sign(mom)
            return w, mom
        w = (1 - lr * (wd + self.wd_lh)) * weight - lr * jnp.sign(grad)
        return w, None


@register
class SignSGD(Signum):
    def __init__(self, **kwargs):
        kwargs.setdefault("momentum", 0.0)
        super().__init__(**kwargs)


@register
class FTML(Optimizer):
    """Follow the Moving Leader (reference: optimizer.py:789)."""

    def __init__(self, beta1=0.6, beta2=0.999, epsilon=1e-8, **kwargs):
        super().__init__(**kwargs)
        self.beta1 = beta1
        self.beta2 = beta2
        self.epsilon = epsilon

    def create_state(self, index, weight):
        return (_zeros_like(weight), _zeros_like(weight), _zeros_like(weight))

    def step(self, weight, grad, state, lr, wd, t):
        prev_d, prev_v, prev_z = state
        g = grad + wd * weight
        v = self.beta2 * prev_v + (1 - self.beta2) * g * g
        d = (1 - self.beta1 ** t) / lr * (
            jnp.sqrt(v / (1 - self.beta2 ** t)) + self.epsilon)
        sigma = d - self.beta1 * prev_d
        z = self.beta1 * prev_z + (1 - self.beta1) * g - sigma * weight
        w = -z / d
        return w, (d, v, z)


@register
class LARS(Optimizer):
    """Layer-wise Adaptive Rate Scaling (reference: optimizer.py:871)."""

    def __init__(self, momentum=0.0, lazy_update=True, eta=0.001, eps=0,
                 **kwargs):
        super().__init__(**kwargs)
        self.momentum = momentum
        self.eta = eta
        self.eps = eps

    def create_state(self, index, weight):
        if self.momentum != 0.0:
            return _zeros_like(weight)
        return None

    def step(self, weight, grad, state, lr, wd, t):
        w_norm = jnp.linalg.norm(weight.ravel())
        g_norm = jnp.linalg.norm(grad.ravel())
        ratio = jnp.where(
            (w_norm > 0) & (g_norm > 0),
            self.eta * w_norm / (g_norm + wd * w_norm + self.eps), 1.0)
        lr_adj = lr * ratio
        g = grad + wd * weight
        if self.momentum == 0.0:
            return weight - lr_adj * g, None
        mom = self.momentum * state + lr_adj * g
        return weight - mom, mom


@register
class LBSGD(Optimizer):
    """Large-batch SGD with warmup strategies (reference: optimizer.py:1038).
    The adaptive-rate core (LARS-style) is kept; warmup strategies linear /
    power2 / sqrt are applied on the lr."""

    # step() reads self.num_update eagerly for the warmup multiplier — in a
    # fused jit step the multiplier would constant-fold at trace time and
    # freeze the warmup schedule.
    jit_safe = False

    def __init__(self, momentum=0.0, multi_precision=False,
                 warmup_strategy="linear", warmup_epochs=5, batch_scale=1,
                 updates_per_epoch=32, begin_epoch=0, num_epochs=60, **kwargs):
        super().__init__(multi_precision=multi_precision, **kwargs)
        self.momentum = momentum
        self.warmup_strategy = warmup_strategy
        self.warmup_epochs = warmup_epochs
        self.batch_scale = batch_scale
        self.updates_per_epoch = updates_per_epoch
        self.init_updates = begin_epoch * updates_per_epoch
        self.num_epochs = num_epochs
        self.lbmult = 1.0

    def create_state(self, index, weight):
        if self.momentum != 0.0:
            return _zeros_like(weight)
        return None

    def _get_lbmult(self, nup):
        nwup = self.warmup_epochs * self.updates_per_epoch
        strategy = self.warmup_strategy
        maxmult = float(self.batch_scale)
        if nup >= nwup:
            mult = maxmult
        elif nwup <= 1:
            mult = 1.0
        else:
            if strategy == "linear":
                mult = 1.0 + (maxmult - 1) * nup / nwup
            elif strategy == "power2":
                mult = 1.0 + (maxmult - 1) * (nup * nup) / (nwup * nwup)
            elif strategy == "sqrt":
                mult = 1.0 + (maxmult - 1) * math.sqrt(float(nup) / nwup)
            else:
                mult = 1.0
        return mult

    def step(self, weight, grad, state, lr, wd, t):
        self.lbmult = self._get_lbmult(self.num_update)
        lr = lr * self.lbmult
        g = grad + wd * weight
        if self.momentum == 0.0:
            return weight - lr * g, None
        mom = self.momentum * state + lr * g
        return weight - mom, mom


@register
class DCASGD(Optimizer):
    """Delay-compensated async SGD (reference: optimizer.py:1224)."""

    def __init__(self, momentum=0.0, lamda=0.04, **kwargs):
        super().__init__(**kwargs)
        self.momentum = momentum
        self.weight_previous = {}
        self.lamda = lamda

    def create_state(self, index, weight):
        if self.momentum == 0.0:
            return (None, _wrap(jnp.asarray(weight._data)))
        return (_zeros_like(weight), _wrap(jnp.asarray(weight._data)))

    def step(self, weight, grad, state, lr, wd, t):
        mom, previous_weight = state
        g = grad + wd * weight
        comp = g + self.lamda * g * g * (weight - previous_weight)
        if mom is None:
            new_mom = None
            delta = -lr * comp
        else:
            new_mom = self.momentum * mom - lr * comp
            delta = new_mom
        new_w = weight + delta
        return new_w, (new_mom, new_w)


@register
class NAG(Optimizer):
    """Nesterov accelerated SGD (reference: optimizer.py:1276)::

        state = momentum * state + grad + wd * weight
        weight = weight - (lr * (grad + momentum * state))
    """

    def __init__(self, momentum=0.0, **kwargs):
        super().__init__(**kwargs)
        self.momentum = momentum

    def create_state(self, index, weight):
        if self.momentum != 0.0:
            return _zeros_like(weight)
        return None

    def step(self, weight, grad, state, lr, wd, t):
        g = grad + wd * weight
        if self.momentum == 0.0:
            return weight - lr * g, None
        mom = self.momentum * state + g
        return weight - lr * (g + self.momentum * mom), mom


@register
class SGLD(Optimizer):
    """Stochastic Gradient Langevin Dynamics (reference: optimizer.py:1328)."""

    def __init__(self, **kwargs):
        super().__init__(**kwargs)

    def step(self, weight, grad, state, lr, wd, t):
        from .. import random as _random
        import jax
        g = grad + wd * weight
        noise = jax.random.normal(_random.new_eager_seed_key(), weight.shape,
                                  weight.dtype) * jnp.sqrt(
                                      jnp.asarray(lr, weight.dtype))
        return weight - lr / 2 * g + noise, None


@register
class ccSGD(SGD):
    """Deprecated alias of SGD (reference: optimizer.py:1360)."""


@register
class Adam(Optimizer):
    """Adam (reference: optimizer.py:1371)::

        m = beta1*m + (1-beta1)*grad
        v = beta2*v + (1-beta2)*grad**2
        lr_t = lr * sqrt(1-beta2**t)/(1-beta1**t)
        w = w - lr_t * m / (sqrt(v) + eps)
    """

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, lazy_update=True, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.beta1 = beta1
        self.beta2 = beta2
        self.epsilon = epsilon
        self.lazy_update = lazy_update

    def create_state(self, index, weight):
        return (_zeros_like(weight), _zeros_like(weight))

    def step(self, weight, grad, state, lr, wd, t):
        m, v = state
        g = grad + wd * weight
        # t may be a traced array inside a jitted train step — jnp math only
        coef1 = 1.0 - self.beta1 ** t
        coef2 = 1.0 - self.beta2 ** t
        lr_t = lr * jnp.sqrt(coef2) / coef1
        m = self.beta1 * m + (1.0 - self.beta1) * g
        v = self.beta2 * v + (1.0 - self.beta2) * g * g
        w = weight - lr_t * m / (jnp.sqrt(v) + self.epsilon)
        return w, (m, v)

    def step_rows(self, weight, rows, grad_rows, state, lr, wd, t):
        """Lazy row_sparse Adam: moments and weights update ONLY on `rows`
        (reference optimizer.py:1371 adam lazy_update)."""
        m, v = state
        g = grad_rows + wd * weight[rows]
        coef1 = 1.0 - self.beta1 ** t
        coef2 = 1.0 - self.beta2 ** t
        lr_t = lr * jnp.sqrt(coef2) / coef1
        m_rows = self.beta1 * m[rows] + (1.0 - self.beta1) * g
        v_rows = self.beta2 * v[rows] + (1.0 - self.beta2) * g * g
        w = weight.at[rows].add(
            -lr_t * m_rows / (jnp.sqrt(v_rows) + self.epsilon))
        return w, (m.at[rows].set(m_rows), v.at[rows].set(v_rows))


@register
class AdaGrad(Optimizer):
    """AdaGrad (reference: optimizer.py:1457)."""

    def __init__(self, eps=1e-7, **kwargs):
        super().__init__(**kwargs)
        self.float_stable_eps = eps

    def create_state(self, index, weight):
        return _zeros_like(weight)

    def step(self, weight, grad, state, lr, wd, t):
        g = grad + wd * weight
        hist = state + g * g
        w = weight - lr * g / (jnp.sqrt(hist) + self.float_stable_eps)
        return w, hist


@register
class RMSProp(Optimizer):
    """RMSProp, centered or not (reference: optimizer.py:1504)."""

    def __init__(self, learning_rate=0.001, gamma1=0.9, gamma2=0.9,
                 epsilon=1e-8, centered=False, clip_weights=None, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.gamma1 = gamma1
        self.gamma2 = gamma2
        self.centered = centered
        self.epsilon = epsilon
        self.clip_weights = clip_weights

    def create_state(self, index, weight):
        if self.centered:
            return (_zeros_like(weight), _zeros_like(weight), _zeros_like(weight))
        return (_zeros_like(weight),)

    def step(self, weight, grad, state, lr, wd, t):
        g = grad + wd * weight
        if self.centered:
            n, gm, delta = state
            n = (1 - self.gamma1) * g * g + self.gamma1 * n
            gm = (1 - self.gamma1) * g + self.gamma1 * gm
            delta = self.gamma2 * delta - lr * g / jnp.sqrt(
                n - gm * gm + self.epsilon)
            w = weight + delta
            if self.clip_weights:
                w = jnp.clip(w, -self.clip_weights, self.clip_weights)
            return w, (n, gm, delta)
        (n,) = state
        n = (1 - self.gamma1) * g * g + self.gamma1 * n
        w = weight - lr * g / jnp.sqrt(n + self.epsilon)
        if self.clip_weights:
            w = jnp.clip(w, -self.clip_weights, self.clip_weights)
        return w, (n,)


@register
class AdaDelta(Optimizer):
    """AdaDelta (reference: optimizer.py:1603)."""

    def __init__(self, rho=0.90, epsilon=1e-5, **kwargs):
        super().__init__(**kwargs)
        self.rho = rho
        self.epsilon = epsilon

    def create_state(self, index, weight):
        return (_zeros_like(weight), _zeros_like(weight))

    def step(self, weight, grad, state, lr, wd, t):
        acc_g, acc_delta = state
        g = grad + wd * weight
        acc_g = self.rho * acc_g + (1.0 - self.rho) * g * g
        delta = jnp.sqrt(acc_delta + self.epsilon) / jnp.sqrt(
            acc_g + self.epsilon) * g
        acc_delta = self.rho * acc_delta + (1.0 - self.rho) * delta * delta
        return weight - delta, (acc_g, acc_delta)


@register
class Ftrl(Optimizer):
    """FTRL-proximal (reference: optimizer.py:1655)."""

    def __init__(self, lamda1=0.01, learning_rate=0.1, beta=1, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.lamda1 = lamda1
        self.beta = beta

    def create_state(self, index, weight):
        return (_zeros_like(weight), _zeros_like(weight))  # z, n

    def step(self, weight, grad, state, lr, wd, t):
        z, n = state
        g = grad
        sigma = (jnp.sqrt(n + g * g) - jnp.sqrt(n)) / lr
        z = z + g - sigma * weight
        n = n + g * g
        w = ((jnp.sign(z) * self.lamda1 - z)
             / ((self.beta + jnp.sqrt(n)) / lr + wd)
             * (jnp.abs(z) > self.lamda1))
        return w, (z, n)


@register
class Adamax(Optimizer):
    """AdaMax (reference: optimizer.py:1727)."""

    def __init__(self, learning_rate=0.002, beta1=0.9, beta2=0.999, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.beta1 = beta1
        self.beta2 = beta2

    def create_state(self, index, weight):
        return (_zeros_like(weight), _zeros_like(weight))

    def step(self, weight, grad, state, lr, wd, t):
        m, u = state
        g = grad + wd * weight
        lr_t = lr / (1.0 - self.beta1 ** t)
        m = self.beta1 * m + (1.0 - self.beta1) * g
        u = jnp.maximum(self.beta2 * u, jnp.abs(g))
        return weight - lr_t * m / (u + 1e-8), (m, u)


@register
class Nadam(Optimizer):
    """Nesterov Adam (reference: optimizer.py:1787)."""

    # step() mutates self.m_schedule (host-side running product) — traced
    # into a compiled program the mutation would happen once at trace time
    # instead of every step.
    jit_safe = False

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, schedule_decay=0.004, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.beta1 = beta1
        self.beta2 = beta2
        self.epsilon = epsilon
        self.schedule_decay = schedule_decay
        self.m_schedule = 1.0

    def create_state(self, index, weight):
        return (_zeros_like(weight), _zeros_like(weight))

    def step(self, weight, grad, state, lr, wd, t):
        m, v = state
        g = grad + wd * weight
        momentum_t = self.beta1 * (1.0 - 0.5 * 0.96 ** (t * self.schedule_decay))
        momentum_t_1 = self.beta1 * (1.0 - 0.5 * 0.96 ** ((t + 1) * self.schedule_decay))
        self.m_schedule = self.m_schedule * momentum_t
        m_schedule_next = self.m_schedule * momentum_t_1
        grad_prime = g / (1.0 - self.m_schedule)
        m = self.beta1 * m + (1.0 - self.beta1) * g
        v = self.beta2 * v + (1.0 - self.beta2) * g * g
        m_prime = m / (1.0 - m_schedule_next)
        v_prime = v / (1.0 - self.beta2 ** t)
        m_bar = ((1.0 - momentum_t) * grad_prime + momentum_t_1 * m_prime)
        w = weight - lr * m_bar / (jnp.sqrt(v_prime) + self.epsilon)
        return w, (m, v)


@register
class Test(Optimizer):
    """Mock optimizer for kvstore tests (reference: optimizer.py:1904)."""

    def __init__(self, **kwargs):
        super().__init__(**kwargs)

    def create_state(self, index, weight):
        return _zeros_like(weight)

    def step(self, weight, grad, state, lr, wd, t):
        return weight + grad * self.rescale_grad, state


@register
class GroupAdaGrad(Optimizer):
    """Adagrad with per-row (group) accumulation (reference:
    python/mxnet/contrib/optimizer.py GroupAdaGrad)."""

    def __init__(self, learning_rate=0.05, eps=1e-5, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.float_stable_eps = eps

    def create_state(self, index, weight):
        return _wrap(jnp.zeros((weight.shape[0], 1), weight._data.dtype))

    def step(self, weight, grad, state, lr, wd, t):
        assert wd == 0, "Weight decay is not supported for GroupAdaGrad"
        hist = state + jnp.mean(grad * grad, axis=tuple(range(1, grad.ndim)),
                                keepdims=True).reshape(state.shape)
        div = lr * grad / (jnp.sqrt(hist).reshape(
            (-1,) + (1,) * (grad.ndim - 1)) + self.float_stable_eps)
        return weight - div, hist


class Updater:
    """KVStore-side updater closure (reference: optimizer.py:1943)."""

    def __init__(self, optimizer):
        self.optimizer = optimizer
        self.states = {}
        self.states_synced = {}
        self.aggregate_updates = optimizer.aggregate_num > 0

    def __call__(self, index, grad, weight):
        if not isinstance(index, (list, tuple)):
            indices = [index]
            grads = [grad]
            weights = [weight]
        else:
            indices = index
            grads = grad
            weights = weight
        for i, (idx, g, w) in enumerate(zip(indices, grads, weights)):
            if idx not in self.states:
                self.states[idx] = self.optimizer.create_state_multi_precision(idx, w)
                self.states_synced[idx] = True
            self.optimizer.update_multi_precision(idx, w, g, self.states[idx])

    def sync_state_context(self, state, context):
        return state

    def set_states(self, states):
        states = pickle.loads(states)
        if isinstance(states, tuple) and len(states) == 2:
            states, self.optimizer = states

        def _nd_state(s):
            if s is None:
                return None
            if isinstance(s, tuple):
                return tuple(_nd_state(x) for x in s)
            if isinstance(s, _np.ndarray):
                return _wrap(jnp.asarray(s))
            return s

        self.states = {k: _nd_state(v) for k, v in states.items()}
        self.states_synced = dict.fromkeys(self.states.keys(), False)

    def get_states(self, dump_optimizer=False):
        def _np_state(s):
            if s is None:
                return None
            if isinstance(s, NDArray):
                return s.asnumpy()
            if isinstance(s, (list, tuple)):
                return tuple(_np_state(x) for x in s)
            return s
        if dump_optimizer:
            return pickle.dumps(({k: _np_state(v) for k, v in self.states.items()},
                                 self.optimizer))
        return pickle.dumps({k: _np_state(v) for k, v in self.states.items()})


def get_updater(optimizer):
    return Updater(optimizer)
