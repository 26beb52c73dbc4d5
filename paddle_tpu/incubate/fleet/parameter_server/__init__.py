"""Parameter-server fleet facade.

Reference: python/paddle/fluid/incubate/fleet/parameter_server/
(distribute_transpiler/__init__.py DistributedTranspiler fleet, and
pslib/ for Baidu PSLib): `fleet.init(role)` →
`fleet.distributed_optimizer(opt).minimize(loss)` → servers run
`fleet.init_server(); fleet.run_server()`, workers run
`fleet.init_worker(); exe.run(fleet.main_program, ...);
fleet.stop_worker()`.

TPU-native split:
- **No server endpoints configured** (a TPU pod): dense parameters use
  ZeRO-style sharding — optimizer state shards over the dp axis ON
  DEVICE (ReduceStrategy.Reduce, compiler.py) and XLA's
  reduce-scatter/all-gather replace the send/recv fabric. This is the
  idiomatic "parameters updated where they live" on TPU.
- **Server endpoints configured** (CPU PS cluster / asynchronous SGD /
  >HBM tables): the REAL PS runtime — DistributeTranspiler splits the
  optimize ops server-side, pservers run ListenAndServ over the native
  tensor_rpc transport, and ``fleet.main_program`` is a
  CompiledProgram-compatible wrapper that routes ``exe.run`` through
  the send/recv step, so the reference's training loop runs unchanged.
"""

from __future__ import annotations

from .... import compiler as compiler_mod
from ....core.enforce import UnavailableError, enforce
from ..base.fleet_base import DistributedOptimizer
from ..collective import Collective, DistributedStrategy

__all__ = ["fleet", "ParameterServerFleet", "PSDistributedOptimizer"]


class _PSTrainerProgram:
    """CompiledProgram-shaped wrapper: exe.run(fleet.main_program, ...)
    executes one full PS step (local fwd+bwd, grad sends, barrier,
    param recv) — the role the send/recv-rewritten trainer program
    plays in the reference."""

    _is_compiled = True

    def __init__(self, runtime):
        self._rt = runtime
        self.program = runtime.program

    def run(self, exe, feed, fetch_list, scope, return_numpy,
            use_program_cache=True, validate_feed=True, donate=True,
            *, phases=None):
        # validate_feed/donate/phases are accepted for run()-protocol
        # parity; the PS runtime validates feeds in its own local-step
        # executor run (which keeps the default donation behavior and
        # books its own entry-point seconds)
        return self._rt.run_step(exe, feed or {},
                                 fetch_list=fetch_list or [],
                                 return_numpy=return_numpy,
                                 scope=scope)


class ParameterServerFleet(Collective):
    """PS-mode facade: real pservers when the role maker carries
    server endpoints, ZeRO sharding otherwise."""

    def __init__(self):
        super().__init__()
        self._transpiler = None
        self._pserver = None
        self._ps_trainer = None

    def _init_impl(self):
        rm = self._rm()
        if rm.is_server() or rm.get_pserver_endpoints():
            # PS processes form no device mesh: servers never touch an
            # accelerator, workers talk to servers over DCN (the
            # collective multihost bootstrap is for pod workers only)
            return
        super()._init_impl()

    def _server_mode(self):
        return bool(self._role_maker is not None and
                    self._rm().get_pserver_endpoints())

    def distributed_optimizer(self, optimizer, strategy=None):
        strategy = strategy or DistributedStrategy()
        strategy.build_strategy.reduce_strategy = \
            compiler_mod.BuildStrategy.ReduceStrategy.Reduce
        self._optimizer = PSDistributedOptimizer(self, optimizer,
                                                 strategy)
        return self._optimizer

    # -- PS wiring (called by PSDistributedOptimizer.minimize) -------------
    def _setup_ps(self, loss, startup_program, sync_mode=True):
        from ....framework import (default_main_program,
                                   default_startup_program)
        from ....transpiler import DistributeTranspiler
        rm = self._rm()
        t = DistributeTranspiler()
        t.transpile(
            trainer_id=max(rm.worker_index(), 0),
            program=loss.block.program if hasattr(loss, "block")
            else default_main_program(),
            startup_program=startup_program or
            default_startup_program(),
            pservers=",".join(rm.get_pserver_endpoints()),
            trainers=rm.worker_num(),
            sync_mode=sync_mode)
        self._transpiler = t

    # -- server side --------------------------------------------------------
    def init_server(self, model_dir=None, snapshot_dir=None,
                    lease_timeout_s=None, allow_degraded=None):
        """``snapshot_dir`` arms durable shard snapshots + restart
        recovery (checkpoint_notify analog); ``lease_timeout_s`` arms
        trainer liveness leases (workers must then pass a heartbeat
        interval to init_worker), with ``allow_degraded`` choosing
        evict-and-continue over BarrierAborted."""
        if not self._server_mode():
            raise UnavailableError(
                "no pserver endpoints configured: dense state is "
                "ZeRO-sharded on device (ReduceStrategy.Reduce); to "
                "run real pservers set PADDLE_PSERVERS_IP_PORT_LIST "
                "or UserDefinedRoleMaker(server_endpoints=[...])")
        enforce(self._transpiler is not None,
                "call distributed_optimizer(...).minimize(loss) first")
        from ....distributed import PServerRuntime
        rm = self._rm()
        ep = rm.get_pserver_endpoints()[rm.server_index()]
        self._pserver = PServerRuntime(self._transpiler, ep,
                                       snapshot_dir=snapshot_dir,
                                       lease_timeout_s=lease_timeout_s,
                                       allow_degraded=allow_degraded)
        if model_dir:
            from .... import io as io_mod
            from ....executor import scope_guard
            with scope_guard(self._pserver.scope):
                io_mod.load_persistables(
                    self._pserver.exe, model_dir,
                    self._transpiler.get_pserver_program(ep))
        return self._pserver

    def run_server(self):
        """Serve until every trainer COMPLETEs (the reference's
        exe.run(pserver_program) on listen_and_serv)."""
        enforce(self._pserver is not None, "call init_server() first")
        self._pserver.run()  # run_until_complete starts the server

    # -- worker side --------------------------------------------------------
    def init_worker(self, heartbeat_interval_s=0.0, deadline_s=30.0,
                    retry=None):
        """``heartbeat_interval_s > 0`` starts the liveness lease
        thread (pair with the server's lease_timeout_s); ``deadline_s``
        bounds every RPC; ``retry`` overrides the per-call transparent
        reconnect+retry policy."""
        if not self._server_mode():
            return  # collective path needs no worker bootstrap
        enforce(self._transpiler is not None,
                "call distributed_optimizer(...).minimize(loss) first")
        from ....core.scope import global_scope
        from ....distributed import ParameterServerRuntime
        t = self._transpiler
        rt = ParameterServerRuntime(
            t, t.get_trainer_program(), global_scope(),
            sync_mode=t.sync_mode,
            heartbeat_interval_s=heartbeat_interval_s,
            deadline_s=deadline_s, retry=retry)
        rt.init_params()
        self._ps_trainer = _PSTrainerProgram(rt)

    def stop_worker(self):
        if self._ps_trainer is not None:
            self._ps_trainer._rt.complete()
            self._ps_trainer = None

    @property
    def main_program(self):
        if self._ps_trainer is not None:
            return self._ps_trainer
        return super().main_program


class PSDistributedOptimizer(DistributedOptimizer):
    def __init__(self, fleet_obj, optimizer, strategy):
        super().__init__(optimizer, strategy)
        self._fleet = fleet_obj

    def minimize(self, loss, startup_program=None, parameter_list=None,
                 no_grad_set=None):
        opt_ops, params_grads = self._optimizer.minimize(
            loss, startup_program, parameter_list, no_grad_set)
        if self._fleet._server_mode():
            self._fleet._setup_ps(
                loss, startup_program,
                sync_mode=not getattr(self._strategy, "async_mode",
                                      False))
        else:
            self._fleet._compile(loss, self._strategy)
        return opt_ops, params_grads


fleet = ParameterServerFleet()
