"""The system under test, as the harness sees it: a static-graph
training program of paddle_tpu driven through its public entry points.

This is the ONLY module of the benchmark that imports the program. It
builds what a configuration file names (a graph builder of
``paddle_tpu.models`` with its config class and published sizes, bf16
AMP, Adam), hands it the benchmark's own weights and batch, and exposes
one ``dispatch()`` -- ``Executor.run_repeated`` or ``Executor.run`` over
a mesh, as the traffic file says -- plus the program's own counters.
"""

import functools
import importlib
import inspect

import jax
import jax.numpy as jnp

from benchmark.reference import common as ref_common


class System:
    def __init__(self, config, traffic, seed, devices, args, spec):
        import paddle_tpu as fluid
        from paddle_tpu import compile_cache, parallel
        from paddle_tpu.contrib import mixed_precision as amp

        self.cache_root = compile_cache.enable()
        self.fluid = fluid
        self.args = args
        self.spec = spec
        self.seed = seed
        self.entry = traffic["entry"]
        self.steps_per_dispatch = int(traffic.get("iters", 1))
        train = config["training"]

        b = config["builder"]
        module = importlib.import_module(b["module"])
        config_class = getattr(module, b["config_class"])
        # what the class does not take is the benchmark's alone (the
        # draw of the weights it makes itself)
        takes = inspect.signature(config_class).parameters
        model_cfg = config_class(**{k: v for k, v in args.items()
                                    if k in takes})
        main, startup = fluid.Program(), fluid.Program()
        # the program's dropout streams follow the seed too
        main.random_seed = startup.random_seed = seed % 2147483629 + 1
        with fluid.unique_name.guard():
            with fluid.program_guard(main, startup):
                out = getattr(module, b["graph"])(model_cfg)
                self.loss = out[b.get("loss_index", 0)]
                opt = getattr(fluid.optimizer, train["optimizer"])(
                    learning_rate=train["learning_rate"],
                    beta1=train["beta1"], beta2=train["beta2"],
                    epsilon=train["epsilon"])
                if train.get("amp"):
                    opt = amp.decorate(opt, dest_dtype=train["amp"])
                opt.minimize(self.loss)
        self.main = main
        self.scope = fluid.Scope()
        place = fluid.TPUPlace(0) if devices[0].platform == "tpu" \
            else fluid.CPUPlace()
        self.exe = fluid.Executor(place)
        self.startup = startup
        params = {p.name: tuple(p.shape) for p in main.all_parameters()}
        want = {n: tuple(s) for n, s, _ in spec}
        if params != want:
            odd = sorted(set(params.items()) ^ set(want.items()))
            raise SystemExit(
                "the program's parameters differ from the reference's "
                "param_spec: %s" % odd[:8])
        self.reseed(seed)
        self.param_names = [n for n, _, _ in spec]
        block = main.global_block()
        self.moment1 = {}
        for n in self.param_names:
            m = [v for v in block.vars
                 if v.startswith(n + "_moment1_")]
            if len(m) != 1:
                raise SystemExit("no single first moment for %s: %s"
                                 % (n, m))
            self.moment1[n] = m[0]

        mesh_axes = traffic.get("mesh")
        self.target = main
        self.mesh = None
        if mesh_axes:
            n = 1
            for v in mesh_axes.values():
                n *= v
            self.mesh = parallel.make_mesh(mesh_axes, devices[:n])
            self.target = fluid.CompiledProgram(main).with_data_parallel(
                loss_name=self.loss.name, mesh=self.mesh)
        self.feed = None

    def reseed(self, seed):
        """Fresh optimizer state from the startup program, and the
        benchmark's weights for ``seed`` in place of its own."""
        self.seed = seed
        self.scope.drop_all()    # startup's executable takes no state
        with self.fluid.scope_guard(self.scope):
            self.exe.run(self.startup)
        for name, value in ref_common.init_params(self.spec,
                                                  seed).items():
            self.scope.set_var(name, value)

    def set_batch(self, batch):
        names = {n for n, v in self.main.global_block().vars.items()
                 if getattr(v, "is_data", False)}
        feed = {k: v for k, v in batch.items()
                if not names or k in names}
        if self.mesh is not None:
            self.feed = {k: jax.device_put(
                v, self.target.feed_sharding(v.shape, k))
                for k, v in feed.items()}
        else:
            self.feed = {k: jnp.asarray(v) for k, v in feed.items()}

    def dispatch(self):
        """Enqueue one dispatch; the loss stays on the device."""
        with self.fluid.scope_guard(self.scope):
            if self.entry == "run_repeated":
                out, = self.exe.run_repeated(
                    self.target, feed=self.feed, fetch_list=[self.loss],
                    iters=self.steps_per_dispatch, return_numpy=False)
            elif self.entry == "run":
                out, = self.exe.run(
                    self.target, feed=self.feed, fetch_list=[self.loss],
                    return_numpy=False)
            else:
                raise SystemExit("unknown entry %r" % self.entry)
        return out

    def state_norms(self):
        """Per leaf, on the device: the norm of Adam's first moment,
        and the norm and the count of changed elements of the
        parameters' change since the benchmark's weights. Those are
        made again from the seed by the very call that made them (a
        draw compiled into another program may round otherwise, and an
        element that differs in its last bit would count as moved) and
        are not kept."""
        p = {n: self.scope.find_var(n) for n in self.param_names}
        m = {n: self.scope.find_var(v) for n, v in self.moment1.items()}
        return _state_norms(p, m, ref_common.init_params(self.spec,
                                                         self.seed))

    def loss_scaling(self):
        """AMP's dynamic loss-scaling state, as device scalars: a scale
        under its initial value, or a streak of bad steps, means steps
        whose gradients were not finite and were skipped."""
        names = ("loss_scaling_0", "loss_scaling_good_steps",
                 "loss_scaling_bad_steps")
        return {n: self.scope.find_var(n) for n in names
                if self.scope.has_var(n)}

    def telemetry(self):
        return self.exe.telemetry(scope=self.scope)

    def artifacts(self):
        return [{k: r.get(k) for k in ("entry", "build_seconds",
                                       "from_cache")}
                | {"mosaic_calls": (r.get("optimized_hlo") or "").count(
                    'custom_call_target="tpu_custom_call"')}
                for r in self.exe.aot_artifacts()]

    def free(self):
        """Drop every device buffer the program holds."""
        self.scope.drop_all()
        if hasattr(self.exe, "close"):
            self.exe.close()
        self.feed = None
        self.exe = self.target = self.main = None
        jax.clear_caches()


@functools.partial(jax.jit, donate_argnums=(2,))
def _state_norms(p, m, p0):
    return {"m1": ref_common.leaf_norms(m),
            **ref_common.leaf_change(p, p0)}


def build(config, traffic, seed, devices, args, spec):
    return System(config, traffic, seed, devices, args, spec)
