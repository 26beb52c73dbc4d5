"""Optimizers — graph-building front-end over ops/optimizer_ops.py.

Reference: python/paddle/fluid/optimizer.py (Optimizer:50, minimize:565 =
backward:441 + apply_gradients:499, _create_optimization_pass:339
creating accumulators + per-param update ops; 12 concrete optimizers
SGD:608 ... Lamb:2074).

The structure is preserved: optimizer state (moments, beta powers) are
persistable vars; ``minimize`` appends backward ops then one update op
per parameter. On TPU all updates live in the same XLA program as the
step, so the reference's fuse_all_optimizer_ops pass
(fuse_optimizer_ops_pass/) is unnecessary — XLA fuses them.
"""

from __future__ import annotations

from contextlib import contextmanager
from typing import Dict, List, Optional

from . import framework, unique_name
from .backward import append_backward
from .core.enforce import enforce
from .framework import Variable, default_main_program, program_guard
from .layer_helper import LayerHelper
from .layers import tensor as tensor_layers
from .regularizer import append_regularization_ops


class Optimizer:
    """Reference: optimizer.py:50."""

    def __init__(self, learning_rate, regularization=None, name=None):
        self._learning_rate = learning_rate
        self.regularization = regularization
        self._name = name
        self._learning_rate_map: Dict[int, Variable] = {}
        self._accumulators: Dict[str, Dict[str, Variable]] = {}
        self._accumulate_steps = 1
        self.type = self.__class__.__name__.lower()

    # -- learning rate -----------------------------------------------------
    def _create_global_learning_rate(self):
        program = default_main_program()
        if id(program) in self._learning_rate_map:
            return
        if isinstance(self._learning_rate, Variable):
            self._learning_rate_map[id(program)] = self._learning_rate
            return
        lr = tensor_layers.create_global_var(
            shape=(), value=float(self._learning_rate), dtype="float32",
            persistable=True,
            name=unique_name.generate("learning_rate"))
        self._learning_rate_map[id(program)] = lr

    def _global_learning_rate(self, program=None):
        program = program or default_main_program()
        return self._learning_rate_map.get(id(program))

    def _create_param_lr(self, param_and_grad):
        param = param_and_grad[0]
        base = self._global_learning_rate()
        param_lr = (param.optimize_attr or {}).get("learning_rate", 1.0)
        if param_lr == 1.0:
            return base
        from .layers import nn
        return nn.scale(base, scale=float(param_lr))

    # -- accumulators ------------------------------------------------------
    def _add_accumulator(self, name, param, dtype=None, fill_value=0.0,
                         shape=None):
        if name in self._accumulators and \
                param.name in self._accumulators[name]:
            return self._accumulators[name][param.name]
        shape = tuple(shape if shape is not None else param.shape)
        var = tensor_layers.create_global_var(
            shape=shape, value=float(fill_value),
            dtype=dtype or param.dtype, persistable=True,
            name=unique_name.generate(param.name + "_" + name))
        self._accumulators.setdefault(name, {})[param.name] = var
        return var

    def _get_accumulator(self, name, param):
        return self._accumulators[name][param.name]

    # -- abstract per-optimizer hook ---------------------------------------
    def _create_accumulators(self, block, parameters):
        pass

    def _append_optimize_op(self, block, param_and_grad):
        raise NotImplementedError

    def _finish_update(self, block, params_grads):
        pass

    # -- public API --------------------------------------------------------
    def backward(self, loss, startup_program=None, parameter_list=None,
                 no_grad_set=None, callbacks=None):
        return append_backward(loss, parameter_list, no_grad_set,
                               callbacks)

    def _append_grad_accumulation(self, block, params_grads, k):
        """Gradient accumulation over ``k`` micro-steps — the TPU-native
        analog of the reference's batch-merge pass
        (framework/ir/multi_batch_merge_pass.cc): instead of replicating
        the fwd/bwd subgraph k times, ONE program keeps a per-param
        running-sum accumulator + a step counter, and the update ops are
        gated (the executor selects old vs updated state) so parameters
        and optimizer moments change only every k-th run."""
        counter = tensor_layers.create_global_var(
            shape=(), value=0.0, dtype="int32", persistable=True,
            name=unique_name.generate("grad_acc_counter"))
        helper = LayerHelper("grad_acc")
        should = helper.create_variable_for_type_inference(
            "bool", stop_gradient=True)
        # inserted at the FRONT of the block so the gate value exists
        # before any op that must be gated — including LR-schedule step
        # counters appended during forward construction
        block.append_op(
            type="accum_steps_counter", inputs={"Counter": [counter]},
            outputs={"CounterOut": [counter], "ShouldApply": [should]},
            attrs={"k": int(k), "op_role": "optimize"}, index=0)
        # LR schedules must advance once per APPLIED update, not once
        # per micro-step (the reference batch-merge pass gates the whole
        # optimize section, lr-decay ops included)
        for op in block.ops:
            if any("@LR_DECAY_COUNTER@" in n
                   for n in op.output_arg_names):
                op.attrs["gate"] = should.name
        new_pg = []
        for p, g in params_grads:
            if g is None:
                new_pg.append((p, g))
                continue
            acc = tensor_layers.create_global_var(
                shape=tuple(p.shape), value=0.0, dtype=g.dtype,
                persistable=True,
                name=unique_name.generate(p.name + "_grad_acc"))
            g_eff = block.create_var(
                name=unique_name.generate(g.name + ".window_mean"),
                shape=tuple(p.shape), dtype=g.dtype, stop_gradient=True)
            block.append_op(
                type="grad_accumulate",
                inputs={"Acc": [acc], "Grad": [g],
                        "ShouldApply": [should]},
                outputs={"AccOut": [acc], "GradOut": [g_eff]},
                attrs={"k": float(k), "op_role": "optimize"})
            new_pg.append((p, g_eff))
        return new_pg, should

    def apply_gradients(self, params_grads):
        # update machinery appended through layers.* helpers
        # (regularizers, clip, accumulation gates) must carry the
        # optimize role so clone(for_test=True) prunes it with the
        # backward ops it reads (framework.op_role_guard)
        with framework.op_role_guard(default_main_program(),
                                     "optimize"), \
                framework.name_scope("optimizer"):
            return self._apply_gradients_impl(params_grads)

    def _apply_gradients_impl(self, params_grads):
        params_grads = sorted(params_grads, key=lambda x: x[0].name)
        params_grads = append_regularization_ops(params_grads,
                                                 self.regularization)
        block = default_main_program().global_block()
        gate = None
        if self._accumulate_steps > 1:
            params_grads, gate = self._append_grad_accumulation(
                block, params_grads, self._accumulate_steps)
        self._create_global_learning_rate()
        self._create_accumulators(
            block, [p for p, g in params_grads if g is not None])
        # subclasses that append EXTRA stateful ops (DGC's u/v + step
        # counter) must gate them too — exposed for _append_optimize_op
        self._accum_gate = gate
        optimize_ops = []
        for pg in params_grads:
            if pg[1] is None:
                continue
            op = self._append_optimize_op(block, pg)
            if gate is not None and op is not None:
                op.attrs["gate"] = gate.name
            optimize_ops.append(op)
        self._accum_gate = None
        self._finish_update(block, params_grads)
        return optimize_ops

    def minimize(self, loss, startup_program=None, parameter_list=None,
                 no_grad_set=None, grad_clip=None, accumulate_steps=None):
        """``accumulate_steps=k`` applies the update once per k runs on
        the mean of the k gradients (static-graph mode only; gradient
        clipping then acts on each micro-gradient)."""
        from . import dygraph
        if dygraph.enabled():
            # eager path: tape backward + in-place param updates via the
            # same optimizer op lowerings (dygraph/optimizer_eager.py)
            from .dygraph.optimizer_eager import apply_dygraph
            params_grads = apply_dygraph(self, loss, parameter_list,
                                         grad_clip=grad_clip)
            return [], params_grads
        if accumulate_steps is None:
            self._accumulate_steps = 1
        else:
            enforce(int(accumulate_steps) >= 1,
                    "accumulate_steps must be >= 1")
            self._accumulate_steps = int(accumulate_steps)
        params_grads = self.backward(loss, startup_program,
                                     parameter_list, no_grad_set)
        if grad_clip is not None:
            from .clip import append_gradient_clip_ops
            with framework.op_role_guard(default_main_program(),
                                         "optimize"), \
                    framework.name_scope("clip"):
                params_grads = append_gradient_clip_ops(params_grads,
                                                        grad_clip)
        optimize_ops = self.apply_gradients(params_grads)
        return optimize_ops, params_grads


class SGDOptimizer(Optimizer):
    """Reference: optimizer.py:608."""

    def _append_optimize_op(self, block, param_and_grad):
        param, grad = param_and_grad
        return block.append_op(
            type="sgd",
            inputs={"Param": [param], "Grad": [grad],
                    "LearningRate": [self._create_param_lr(
                        param_and_grad)]},
            outputs={"ParamOut": [param]},
            attrs={"op_role": "optimize"})


class MomentumOptimizer(Optimizer):
    """Reference: optimizer.py Momentum."""

    def __init__(self, learning_rate, momentum, use_nesterov=False,
                 regularization=None, name=None):
        super().__init__(learning_rate, regularization, name)
        self._momentum = momentum
        self._use_nesterov = use_nesterov

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("velocity", p)

    def _append_optimize_op(self, block, param_and_grad):
        param, grad = param_and_grad
        velocity = self._get_accumulator("velocity", param)
        return block.append_op(
            type="momentum",
            inputs={"Param": [param], "Grad": [grad],
                    "Velocity": [velocity],
                    "LearningRate": [self._create_param_lr(
                        param_and_grad)]},
            outputs={"ParamOut": [param], "VelocityOut": [velocity]},
            attrs={"mu": self._momentum,
                   "use_nesterov": self._use_nesterov,
                   "op_role": "optimize"})


class LarsMomentumOptimizer(Optimizer):
    """Reference: optimizer.py LarsMomentumOptimizer."""

    def __init__(self, learning_rate, momentum, lars_coeff=0.001,
                 lars_weight_decay=0.0005, regularization=None,
                 name=None):
        super().__init__(learning_rate, regularization, name)
        self._momentum = momentum
        self._lars_coeff = lars_coeff
        self._lars_weight_decay = lars_weight_decay

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("velocity", p)

    def _append_optimize_op(self, block, param_and_grad):
        param, grad = param_and_grad
        velocity = self._get_accumulator("velocity", param)
        return block.append_op(
            type="lars_momentum",
            inputs={"Param": [param], "Grad": [grad],
                    "Velocity": [velocity],
                    "LearningRate": [self._create_param_lr(
                        param_and_grad)]},
            outputs={"ParamOut": [param], "VelocityOut": [velocity]},
            attrs={"mu": self._momentum,
                   "lars_coeff": self._lars_coeff,
                   "lars_weight_decay": self._lars_weight_decay,
                   "op_role": "optimize"})


class DGCMomentumOptimizer(MomentumOptimizer):
    """Deep Gradient Compression momentum (reference: optimizer.py:786
    DGCMomentumOptimizer; details/sparse_all_reduce_op_handle.h;
    arXiv:1712.01887). Sparsifies each parameter's update to the
    top-(1 - sparsity) entries of the locally-accumulated
    momentum-corrected gradient; the residual accumulates until it
    matters. See the ``dgc`` op for the TPU-native formulation (the
    GSPMD psum replaces the NCCL sparse allreduce)."""

    def __init__(self, learning_rate, momentum, rampup_begin_step,
                 rampup_step=1, sparsity=(0.999,), use_nesterov=False,
                 local_grad_clip_norm=None, num_trainers=None,
                 regularization=None, name=None):
        super().__init__(learning_rate, momentum, use_nesterov,
                         regularization, name)
        self._rampup_begin_step = int(rampup_begin_step)
        self._rampup_step = int(rampup_step)
        self._sparsity = tuple(float(s) for s in sparsity)
        self._local_grad_clip_norm = local_grad_clip_norm
        self._num_trainers = num_trainers
        self._step_var = None

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("dgc_u", p)
            self._add_accumulator("dgc_v", p)

    def _append_optimize_op(self, block, param_and_grad):
        param, grad = param_and_grad
        gate = getattr(self, "_accum_gate", None)
        if self._step_var is None:
            self._step_var = tensor_layers.create_global_var(
                shape=(), value=0.0, dtype="int32", persistable=True,
                name=unique_name.generate("dgc_step"))
            counter_op = block.append_op(
                type="cum_step_counter",
                inputs={"X": [self._step_var]},
                outputs={"Out": [self._step_var]},
                attrs={"op_role": "optimize"})
            if gate is not None:
                # under gradient accumulation the DGC step advances
                # once per APPLIED update, not per micro-step
                counter_op.attrs["gate"] = gate.name
        if self._local_grad_clip_norm is not None:
            clipped = block.create_var(
                name=unique_name.generate(grad.name + ".dgc_clip"),
                shape=tuple(param.shape), dtype=grad.dtype,
                stop_gradient=True)
            block.append_op(
                type="clip_by_norm", inputs={"X": [grad]},
                outputs={"Out": [clipped]},
                attrs={"max_norm":
                       float(self._local_grad_clip_norm) *
                       (float(self._num_trainers) ** -0.5
                        if self._num_trainers else 1.0),
                       "op_role": "optimize"})
            grad = clipped
        u = self._get_accumulator("dgc_u", param)
        v = self._get_accumulator("dgc_v", param)
        encoded = block.create_var(
            name=unique_name.generate(grad.name + ".dgc_encoded"),
            shape=tuple(param.shape), dtype=grad.dtype,
            stop_gradient=True)
        dgc_op = block.append_op(
            type="dgc",
            inputs={"U": [u], "V": [v], "Grad": [grad],
                    "CurrentStep": [self._step_var]},
            outputs={"UOut": [u], "VOut": [v],
                     "EncodedGrad": [encoded]},
            attrs={"m": self._momentum,
                   "sparsity": self._sparsity,
                   "rampup_begin_step": self._rampup_begin_step,
                   "rampup_step": self._rampup_step,
                   "use_nesterov": self._use_nesterov,
                   "op_role": "optimize"})
        if gate is not None:
            # u/v accumulators must only advance on the apply step
            dgc_op.attrs["gate"] = gate.name
        # momentum correction folded into u: the final apply is plain
        # sgd on the (sparse) encoded update
        return block.append_op(
            type="sgd",
            inputs={"Param": [param], "Grad": [encoded],
                    "LearningRate": [self._create_param_lr(
                        param_and_grad)]},
            outputs={"ParamOut": [param]},
            attrs={"op_role": "optimize"})


class AdagradOptimizer(Optimizer):
    def __init__(self, learning_rate, epsilon=1e-6, regularization=None,
                 name=None, initial_accumulator_value=0.0):
        super().__init__(learning_rate, regularization, name)
        self._epsilon = epsilon
        self._initial = initial_accumulator_value

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("moment", p, fill_value=self._initial)

    def _append_optimize_op(self, block, param_and_grad):
        param, grad = param_and_grad
        moment = self._get_accumulator("moment", param)
        return block.append_op(
            type="adagrad",
            inputs={"Param": [param], "Grad": [grad],
                    "Moment": [moment],
                    "LearningRate": [self._create_param_lr(
                        param_and_grad)]},
            outputs={"ParamOut": [param], "MomentOut": [moment]},
            attrs={"epsilon": self._epsilon, "op_role": "optimize"})


class AdamOptimizer(Optimizer):
    """Reference: optimizer.py AdamOptimizer (adam_op.cc)."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, regularization=None, name=None,
                 lazy_mode=False):
        super().__init__(learning_rate, regularization, name)
        self._beta1, self._beta2, self._epsilon = beta1, beta2, epsilon
        self._lazy_mode = lazy_mode

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("moment1", p)
            self._add_accumulator("moment2", p)
            self._add_accumulator("beta1_pow_acc", p, shape=(),
                                  fill_value=self._beta1)
            self._add_accumulator("beta2_pow_acc", p, shape=(),
                                  fill_value=self._beta2)

    def _append_optimize_op(self, block, param_and_grad):
        param, grad = param_and_grad
        m1 = self._get_accumulator("moment1", param)
        m2 = self._get_accumulator("moment2", param)
        b1p = self._get_accumulator("beta1_pow_acc", param)
        b2p = self._get_accumulator("beta2_pow_acc", param)
        return block.append_op(
            type="adam",
            inputs={"Param": [param], "Grad": [grad], "Moment1": [m1],
                    "Moment2": [m2], "Beta1Pow": [b1p],
                    "Beta2Pow": [b2p],
                    "LearningRate": [self._create_param_lr(
                        param_and_grad)]},
            outputs={"ParamOut": [param], "Moment1Out": [m1],
                     "Moment2Out": [m2], "Beta1PowOut": [b1p],
                     "Beta2PowOut": [b2p]},
            attrs={"beta1": self._beta1, "beta2": self._beta2,
                   "epsilon": self._epsilon,
                   "lazy_mode": self._lazy_mode,
                   "op_role": "optimize"})


class AdamWOptimizer(AdamOptimizer):
    """Decoupled weight decay (contrib
    extend_optimizer/decoupled_weight_decay analog)."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, weight_decay=0.01, regularization=None,
                 name=None):
        super().__init__(learning_rate, beta1, beta2, epsilon,
                         regularization, name)
        self._weight_decay = weight_decay

    def _append_optimize_op(self, block, param_and_grad):
        param, grad = param_and_grad
        m1 = self._get_accumulator("moment1", param)
        m2 = self._get_accumulator("moment2", param)
        b1p = self._get_accumulator("beta1_pow_acc", param)
        b2p = self._get_accumulator("beta2_pow_acc", param)
        return block.append_op(
            type="adamw",
            inputs={"Param": [param], "Grad": [grad], "Moment1": [m1],
                    "Moment2": [m2], "Beta1Pow": [b1p],
                    "Beta2Pow": [b2p],
                    "LearningRate": [self._create_param_lr(
                        param_and_grad)]},
            outputs={"ParamOut": [param], "Moment1Out": [m1],
                     "Moment2Out": [m2], "Beta1PowOut": [b1p],
                     "Beta2PowOut": [b2p]},
            attrs={"beta1": self._beta1, "beta2": self._beta2,
                   "epsilon": self._epsilon,
                   "weight_decay": self._weight_decay,
                   "op_role": "optimize"})


class AdamaxOptimizer(Optimizer):
    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, regularization=None, name=None):
        super().__init__(learning_rate, regularization, name)
        self._beta1, self._beta2, self._epsilon = beta1, beta2, epsilon

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("moment", p)
            self._add_accumulator("inf_norm", p)
            self._add_accumulator("beta1_pow_acc", p, shape=(),
                                  fill_value=self._beta1)

    def _append_optimize_op(self, block, param_and_grad):
        param, grad = param_and_grad
        moment = self._get_accumulator("moment", param)
        inf_norm = self._get_accumulator("inf_norm", param)
        b1p = self._get_accumulator("beta1_pow_acc", param)
        return block.append_op(
            type="adamax",
            inputs={"Param": [param], "Grad": [grad],
                    "Moment": [moment], "InfNorm": [inf_norm],
                    "Beta1Pow": [b1p],
                    "LearningRate": [self._create_param_lr(
                        param_and_grad)]},
            outputs={"ParamOut": [param], "MomentOut": [moment],
                     "InfNormOut": [inf_norm], "Beta1PowOut": [b1p]},
            attrs={"beta1": self._beta1, "beta2": self._beta2,
                   "epsilon": self._epsilon, "op_role": "optimize"})


class DecayedAdagradOptimizer(Optimizer):
    def __init__(self, learning_rate, decay=0.95, epsilon=1e-6,
                 regularization=None, name=None):
        super().__init__(learning_rate, regularization, name)
        self._decay, self._epsilon = decay, epsilon

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("moment", p)

    def _append_optimize_op(self, block, param_and_grad):
        param, grad = param_and_grad
        moment = self._get_accumulator("moment", param)
        return block.append_op(
            type="decayed_adagrad",
            inputs={"Param": [param], "Grad": [grad],
                    "Moment": [moment],
                    "LearningRate": [self._create_param_lr(
                        param_and_grad)]},
            outputs={"ParamOut": [param], "MomentOut": [moment]},
            attrs={"decay": self._decay, "epsilon": self._epsilon,
                   "op_role": "optimize"})


class AdadeltaOptimizer(Optimizer):
    def __init__(self, learning_rate, epsilon=1e-6, rho=0.95,
                 regularization=None, name=None):
        super().__init__(learning_rate, regularization, name)
        self._epsilon, self._rho = epsilon, rho

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("avg_squared_grad", p)
            self._add_accumulator("avg_squared_update", p)

    def _append_optimize_op(self, block, param_and_grad):
        param, grad = param_and_grad
        asg = self._get_accumulator("avg_squared_grad", param)
        asu = self._get_accumulator("avg_squared_update", param)
        return block.append_op(
            type="adadelta",
            inputs={"Param": [param], "Grad": [grad],
                    "AvgSquaredGrad": [asg], "AvgSquaredUpdate": [asu]},
            outputs={"ParamOut": [param], "AvgSquaredGradOut": [asg],
                     "AvgSquaredUpdateOut": [asu]},
            attrs={"rho": self._rho, "epsilon": self._epsilon,
                   "op_role": "optimize"})


class RMSPropOptimizer(Optimizer):
    def __init__(self, learning_rate, rho=0.95, epsilon=1e-6,
                 momentum=0.0, centered=False, regularization=None,
                 name=None):
        super().__init__(learning_rate, regularization, name)
        self._rho, self._epsilon = rho, epsilon
        self._momentum, self._centered = momentum, centered

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("momentum", p)
            self._add_accumulator("mean_square", p)
            self._add_accumulator("mean_grad", p)

    def _append_optimize_op(self, block, param_and_grad):
        param, grad = param_and_grad
        mom = self._get_accumulator("momentum", param)
        ms = self._get_accumulator("mean_square", param)
        mg = self._get_accumulator("mean_grad", param)
        return block.append_op(
            type="rmsprop",
            inputs={"Param": [param], "Grad": [grad], "Moment": [mom],
                    "MeanSquare": [ms], "MeanGrad": [mg],
                    "LearningRate": [self._create_param_lr(
                        param_and_grad)]},
            outputs={"ParamOut": [param], "MomentOut": [mom],
                     "MeanSquareOut": [ms], "MeanGradOut": [mg]},
            attrs={"rho": self._rho, "epsilon": self._epsilon,
                   "momentum": self._momentum,
                   "centered": self._centered, "op_role": "optimize"})


class FtrlOptimizer(Optimizer):
    def __init__(self, learning_rate, l1=0.0, l2=0.0, lr_power=-0.5,
                 regularization=None, name=None):
        super().__init__(learning_rate, regularization, name)
        self._l1, self._l2, self._lr_power = l1, l2, lr_power

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("squared", p)
            self._add_accumulator("linear", p)

    def _append_optimize_op(self, block, param_and_grad):
        param, grad = param_and_grad
        sq = self._get_accumulator("squared", param)
        lin = self._get_accumulator("linear", param)
        return block.append_op(
            type="ftrl",
            inputs={"Param": [param], "Grad": [grad],
                    "SquaredAccumulator": [sq],
                    "LinearAccumulator": [lin],
                    "LearningRate": [self._create_param_lr(
                        param_and_grad)]},
            outputs={"ParamOut": [param], "SquaredAccumOut": [sq],
                     "LinearAccumOut": [lin]},
            attrs={"l1": self._l1, "l2": self._l2,
                   "lr_power": self._lr_power, "op_role": "optimize"})


class LambOptimizer(Optimizer):
    """Reference: optimizer.py:2074 LambOptimizer."""

    def __init__(self, learning_rate=0.001, lamb_weight_decay=0.01,
                 beta1=0.9, beta2=0.999, epsilon=1e-6,
                 regularization=None, name=None):
        super().__init__(learning_rate, regularization, name)
        self._weight_decay = lamb_weight_decay
        self._beta1, self._beta2, self._epsilon = beta1, beta2, epsilon

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("moment1", p)
            self._add_accumulator("moment2", p)
            self._add_accumulator("beta1_pow_acc", p, shape=(),
                                  fill_value=self._beta1)
            self._add_accumulator("beta2_pow_acc", p, shape=(),
                                  fill_value=self._beta2)

    def _append_optimize_op(self, block, param_and_grad):
        param, grad = param_and_grad
        m1 = self._get_accumulator("moment1", param)
        m2 = self._get_accumulator("moment2", param)
        b1p = self._get_accumulator("beta1_pow_acc", param)
        b2p = self._get_accumulator("beta2_pow_acc", param)
        return block.append_op(
            type="lamb",
            inputs={"Param": [param], "Grad": [grad], "Moment1": [m1],
                    "Moment2": [m2], "Beta1Pow": [b1p],
                    "Beta2Pow": [b2p],
                    "LearningRate": [self._create_param_lr(
                        param_and_grad)]},
            outputs={"ParamOut": [param], "Moment1Out": [m1],
                     "Moment2Out": [m2], "Beta1PowOut": [b1p],
                     "Beta2PowOut": [b2p]},
            attrs={"beta1": self._beta1, "beta2": self._beta2,
                   "epsilon": self._epsilon,
                   "weight_decay": self._weight_decay,
                   "op_role": "optimize"})


def _declare_persistable(block, var):
    """Declare an existing persistable var (by name) inside a fresh
    program so the executor binds it to the scope value — the pattern
    of reference io.py's _clone_var_in_block_."""
    return block.create_var(name=var.name, shape=tuple(var.shape),
                            dtype=var.dtype, persistable=True,
                            stop_gradient=True)


class ModelAverage(Optimizer):
    """Sliding-window parameter averaging (reference: optimizer.py:2222
    ModelAverage + operators/average_accumulates_op). Construct AFTER
    optimizer.minimize: appends an average_accumulates op per parameter
    to the main program; ``apply()`` swaps parameters for their window
    average (eval), ``restore()`` swaps back."""

    def __init__(self, average_window_rate, min_average_window=10000,
                 max_average_window=10000, regularization=None,
                 name=None):
        super().__init__(0.0, regularization, name)
        self.average_window = float(average_window_rate)
        self.min_average_window = int(min_average_window)
        self.max_average_window = int(max_average_window)
        main = default_main_program()
        block = main.global_block()
        self._params = [
            p for p in block.all_parameters()
            if p.trainable
            and getattr(p, "do_model_average", None) is not False]
        for p in self._params:
            self._create_accumulators(block, [p])
            self._append_average_accumulate_op(block, p)
        self._build_programs()

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("sum_1", p)
            self._add_accumulator("sum_2", p)
            self._add_accumulator("sum_3", p)
            self._add_accumulator("num_accumulates", p, dtype="int64",
                                  shape=())
            self._add_accumulator("old_num_accumulates", p,
                                  dtype="int64", shape=())
            self._add_accumulator("num_updates", p, dtype="int64",
                                  shape=())

    def _acc_vars(self, p):
        return [self._get_accumulator(n, p)
                for n in ("sum_1", "sum_2", "sum_3", "num_accumulates",
                          "old_num_accumulates", "num_updates")]

    def _append_average_accumulate_op(self, block, param):
        s1, s2, s3, na, ona, nu = self._acc_vars(param)
        block.append_op(
            type="average_accumulates",
            inputs={"Param": [param], "Sum1": [s1], "Sum2": [s2],
                    "Sum3": [s3], "NumAccumulates": [na],
                    "OldNumAccumulates": [ona], "NumUpdates": [nu]},
            outputs={"Sum1Out": [s1], "Sum2Out": [s2], "Sum3Out": [s3],
                     "NumAccumulatesOut": [na],
                     "OldNumAccumulatesOut": [ona],
                     "NumUpdatesOut": [nu]},
            attrs={"average_window": self.average_window,
                   "min_average_window": self.min_average_window,
                   "max_average_window": self.max_average_window,
                   "op_role": "optimize"})

    def _build_programs(self):
        self._apply_program = framework.Program()
        ab = self._apply_program.global_block()
        self._restore_program = framework.Program()
        rb = self._restore_program.global_block()
        for p in self._params:
            pv = _declare_persistable(ab, p)
            accs = [_declare_persistable(ab, v)
                    for v in self._acc_vars(p)]
            backup = ab.create_var(
                name=p.name + ".model_avg_backup", shape=tuple(p.shape),
                dtype=p.dtype, persistable=True, stop_gradient=True)
            ab.append_op(type="assign", inputs={"X": [pv]},
                         outputs={"Out": [backup]})
            ab.append_op(
                type="model_average_apply",
                inputs={"Sum1": [accs[0]], "Sum2": [accs[1]],
                        "Sum3": [accs[2]], "NumAccumulates": [accs[3]],
                        "OldNumAccumulates": [accs[4]]},
                outputs={"Out": [pv]})
            rpv = _declare_persistable(rb, p)
            rbk = _declare_persistable(rb, backup)
            rb.append_op(type="assign", inputs={"X": [rbk]},
                         outputs={"Out": [rpv]})

    def _append_optimize_op(self, block, param_and_grad):
        raise NotImplementedError(
            "ModelAverage is not a training optimizer; construct it "
            "after optimizer.minimize")

    @contextmanager
    def apply(self, executor, need_restore=True):
        """Swap params for their averages within the context."""
        executor.run(self._apply_program)
        try:
            yield
        finally:
            if need_restore:
                self.restore(executor)

    def restore(self, executor):
        executor.run(self._restore_program)


class ExponentialMovingAverage:
    """EMA of parameters with bias correction (reference:
    optimizer.py:2412). Call ``update()`` after optimizer.minimize to
    append shadow updates to the main program; ``apply()`` swaps in the
    bias-corrected shadow values for evaluation."""

    def __init__(self, decay=0.999, thres_steps=None, name=None):
        self._decay = float(decay)
        self._thres_steps = thres_steps
        self._name = name or ""
        main = default_main_program()
        block = main.global_block()
        self._params = [p for p in block.all_parameters() if p.trainable]
        self._ema = {}
        for p in self._params:
            self._ema[p.name] = tensor_layers.create_global_var(
                shape=tuple(p.shape), value=0.0, dtype=p.dtype,
                persistable=True,
                name=unique_name.generate(p.name + ".ema"))
        self._decay_pow = tensor_layers.create_global_var(
            shape=(), value=1.0, dtype="float32", persistable=True,
            name=unique_name.generate(self._name + "ema_decay_pow"))
        self._build_programs()

    def update(self):
        block = default_main_program().global_block()
        helper = LayerHelper("ema")
        use_thres = self._thres_steps is not None
        for i, p in enumerate(self._params):
            ema = self._ema[p.name]
            inputs = {"Param": [p], "Ema": [ema],
                      "DecayPow": [self._decay_pow]}
            if use_thres:
                inputs["Step"] = [self._thres_steps]
            # decay_pow is shared (the decay schedule is global): only
            # the first op commits it; the rest discard the output
            dp_out = self._decay_pow if i == 0 else \
                helper.create_variable_for_type_inference(
                    "float32", stop_gradient=True)
            block.append_op(
                type="ema_update", inputs=inputs,
                outputs={"EmaOut": [ema], "DecayPowOut": [dp_out]},
                attrs={"decay": self._decay, "use_thres": use_thres,
                       "op_role": "optimize"})

    def _build_programs(self):
        self._apply_program = framework.Program()
        ab = self._apply_program.global_block()
        self._restore_program = framework.Program()
        rb = self._restore_program.global_block()
        for p in self._params:
            pv = _declare_persistable(ab, p)
            ev = _declare_persistable(ab, self._ema[p.name])
            dpv = _declare_persistable(ab, self._decay_pow)
            backup = ab.create_var(
                name=p.name + ".ema_backup", shape=tuple(p.shape),
                dtype=p.dtype, persistable=True, stop_gradient=True)
            ab.append_op(type="assign", inputs={"X": [pv]},
                         outputs={"Out": [backup]})
            ab.append_op(type="ema_apply",
                         inputs={"Ema": [ev], "DecayPow": [dpv]},
                         outputs={"Out": [pv]})
            rpv = _declare_persistable(rb, p)
            rbk = _declare_persistable(rb, backup)
            rb.append_op(type="assign", inputs={"X": [rbk]},
                         outputs={"Out": [rpv]})

    @contextmanager
    def apply(self, executor, need_restore=True):
        executor.run(self._apply_program)
        try:
            yield
        finally:
            if need_restore:
                self.restore(executor)

    def restore(self, executor):
        executor.run(self._restore_program)


# fluid-style aliases (reference exports both names)
SGD = SGDOptimizer
Momentum = MomentumOptimizer
DGCMomentum = DGCMomentumOptimizer
Adagrad = AdagradOptimizer
Adam = AdamOptimizer
AdamW = AdamWOptimizer
Adamax = AdamaxOptimizer
DecayedAdagrad = DecayedAdagradOptimizer
Adadelta = AdadeltaOptimizer
RMSProp = RMSPropOptimizer
Ftrl = FtrlOptimizer
Lamb = LambOptimizer
LarsMomentum = LarsMomentumOptimizer
