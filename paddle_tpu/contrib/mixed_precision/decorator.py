"""Mixed-precision optimizer decorator.

Reference: python/paddle/fluid/contrib/mixed_precision/decorator.py:26
(OptimizerWithMixedPrecision: fp16 forward/backward with fp32 master
weights + static/dynamic loss scaling; decorate():~230).

TPU-native redesign: compute dtype is bfloat16 (MXU-native). Parameters
keep float32 storage and every optimizer update runs in float32 —
master weights by construction, without the reference's separate
master-weight copies. Loss scaling is kept for fp16 parity and for
models whose gradients underflow even in bf16:

  scaled_loss = loss * loss_scaling        (before backward)
  grad        = grad / loss_scaling        (after backward)
  dynamic mode (update_loss_scaling op analog, in-graph):
    all_finite = all(isfinite(g) for g in grads)
    non-finite step: grads zeroed, scale *= decr_ratio, streak reset
    finite step: after incr_every_n_steps consecutive finite steps,
                 scale *= incr_ratio, streak reset
"""

from __future__ import annotations

from ... import layers
from ...core.enforce import enforce
from ...framework import (default_main_program,
                          default_startup_program, name_scope)
from .fp16_lists import AutoMixedPrecisionLists
from .fp16_utils import rewrite_program


class OptimizerWithMixedPrecision:
    """Wraps a regular optimizer (reference: decorator.py:26). Use
    ``decorate()``, not this class directly."""

    def __init__(self, optimizer, amp_lists, init_loss_scaling,
                 use_dynamic_loss_scaling, incr_every_n_steps,
                 decr_every_n_nan_or_inf, incr_ratio, decr_ratio,
                 dest_dtype):
        self._optimizer = optimizer
        self._amp_lists = amp_lists or AutoMixedPrecisionLists()
        self._init_loss_scaling = float(init_loss_scaling)
        self._use_dynamic_loss_scaling = use_dynamic_loss_scaling
        self._incr_every_n_steps = int(incr_every_n_steps)
        self._decr_every_n_nan_or_inf = int(decr_every_n_nan_or_inf)
        self._incr_ratio = float(incr_ratio)
        self._decr_ratio = float(decr_ratio)
        self._dest_dtype = dest_dtype
        self._loss_scaling = None

    def get_loss_scaling(self):
        """The loss-scaling Variable (reference: decorator.py:73)."""
        return self._loss_scaling

    def backward(self, loss, startup_program=None, parameter_list=None,
                 no_grad_set=None, callbacks=None):
        """Rewrite the program to bf16, scale the loss, run backward,
        unscale the grads. Returns (params_grads, scaled_loss)."""
        main = default_main_program()
        rewrite_program(main, self._amp_lists, self._dest_dtype)

        # the loss scaling and its update are, like the casts, AMP's
        # own layer kind in a device trace (framework.name_scope)
        with name_scope("amp"):
            self._loss_scaling = layers.create_global_var(
                shape=[1], value=self._init_loss_scaling,
                dtype="float32", persistable=True,
                name="loss_scaling_0")
            scaled_loss = loss * self._loss_scaling

        params_grads = self._optimizer.backward(
            scaled_loss, startup_program, parameter_list, no_grad_set,
            callbacks)

        # Everything from here on is update machinery: stamp the
        # optimize role so clone(for_test=True) prunes it along with
        # the backward ops it reads (framework.op_role_guard) — a test
        # clone keeping an isfinite(g) op would dangle on the pruned
        # gradient vars.
        from ...framework import op_role_guard
        with op_role_guard(main, "optimize"), name_scope("amp"):
            inv = 1.0 / self._loss_scaling
            if self._use_dynamic_loss_scaling:
                finite = None
                for _p, g in params_grads:
                    f = layers.reduce_all(layers.isfinite(g))
                    finite = f if finite is None else \
                        layers.logical_and(finite, f)
                self._all_finite = finite
                # non-finite step: select zeros (a where, NOT a
                # multiply — inf * 0 would poison the update with NaN)
                # so the step is a no-op (reference:
                # update_loss_scaling zeroes grads on overflow)
                params_grads = [
                    (p, layers.where(finite, g * inv,
                                     layers.zeros_like(g)))
                    for p, g in params_grads]
                self._append_scale_update(finite)
            else:
                params_grads = [(p, g * inv) for p, g in params_grads]
        return params_grads, scaled_loss

    def _append_scale_update(self, finite):
        """In-graph dynamic loss-scale state machine (the reference's
        update_loss_scaling op, loss_scaling.py)."""
        good = layers.create_global_var(
            shape=[1], value=0.0, dtype="float32", persistable=True,
            name="loss_scaling_good_steps")
        bad = layers.create_global_var(
            shape=[1], value=0.0, dtype="float32", persistable=True,
            name="loss_scaling_bad_steps")
        one = layers.ones([1], "float32")
        zero = layers.zeros([1], "float32")
        scale = self._loss_scaling

        good_next = layers.where(finite, good + one, zero)
        bad_next = layers.where(finite, zero, bad + one)
        grow = layers.greater_equal(
            good_next, layers.fill_constant(
                [1], "float32", float(self._incr_every_n_steps)))
        shrink = layers.greater_equal(
            bad_next, layers.fill_constant(
                [1], "float32", float(self._decr_every_n_nan_or_inf)))
        new_scale = layers.where(
            grow, scale * self._incr_ratio,
            layers.where(shrink, scale * self._decr_ratio, scale))
        # scale never drops below 1.0 nor explodes past f32
        new_scale = layers.clip(new_scale, min=1.0, max=3.0e38)
        layers.assign(layers.where(grow, zero, good_next), good)
        layers.assign(layers.where(shrink, zero, bad_next), bad)
        layers.assign(new_scale, scale)

    def apply_gradients(self, params_grads):
        return self._optimizer.apply_gradients(params_grads)

    def minimize(self, loss, startup_program=None, parameter_list=None,
                 no_grad_set=None, grad_clip=None):
        enforce(loss.dtype == "float32",
                "AMP expects a float32 loss (black-list ops keep the "
                "loss head in float32); got %s" % loss.dtype)
        params_grads, scaled_loss = self.backward(
            loss, startup_program, parameter_list, no_grad_set)
        if grad_clip is not None:
            from ...clip import append_gradient_clip_ops
            from ...framework import (default_main_program,
                                      op_role_guard)
            # clip ops read gradient vars: optimize role, or a test
            # clone keeps them dangling (same guard as backward())
            with op_role_guard(default_main_program(), "optimize"), \
                    name_scope("clip"):
                params_grads = append_gradient_clip_ops(params_grads,
                                                        grad_clip)
        optimize_ops = self.apply_gradients(params_grads)
        return optimize_ops, params_grads


def decorate(optimizer, amp_lists=None, init_loss_scaling=2.0 ** 15,
             incr_every_n_steps=1000, decr_every_n_nan_or_inf=2,
             incr_ratio=2.0, decr_ratio=0.8,
             use_dynamic_loss_scaling=True, dest_dtype="bfloat16"):
    """Reference: decorator.py decorate(). ``dest_dtype`` picks the
    low-precision compute type — bfloat16 on TPU (fp16 also accepted
    for parity testing)."""
    return OptimizerWithMixedPrecision(
        optimizer, amp_lists, init_loss_scaling,
        use_dynamic_loss_scaling, incr_every_n_steps,
        decr_every_n_nan_or_inf, incr_ratio, decr_ratio, dest_dtype)
