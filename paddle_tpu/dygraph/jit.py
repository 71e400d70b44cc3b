"""TracedLayer — capture an eager Layer call into a static Program
(parity: python/paddle/fluid/dygraph/jit.py TracedLayer of the reference
line; SURVEY C21 + the round-3 VERDICT's dygraph-to-jit item).

Why it matters on TPU: eager ops dispatch one XLA computation each and pay
the per-call launch floor, so an eager model is launch-bound. Tracing the SAME Layer
object records every executed op into a Program; running that through the
Executor compiles the whole forward into ONE jitted XLA step with the
program cache — static-graph speed from dygraph code, and the artifact
feeds save_inference_model / the serving exporter unchanged.

    with fluid.dygraph.guard():
        model = MyLayer()
        out, traced = fluid.dygraph.TracedLayer.trace(model, [to_variable(x)])
        fast = traced([x2])                 # one jitted step
        traced.save_inference_model("./sd") # standard inference artifact
"""

import numpy as np

from .. import framework
from ..core.scope import Scope, scope_guard
from .base import VarBase, _current_tracer

__all__ = ["TracedLayer"]


class TracedLayer:
    """A static Program recorded from one eager forward, plus the scope
    holding the layer's parameter values. Construct via `trace`."""

    def __init__(self, program, feed_vars, fetch_vars, scope,
                 param_sources=()):
        self.program = program
        self._feed_vars = feed_vars
        self._fetch_vars = fetch_vars
        self._scope = scope
        # (scope name, live VarBase) pairs: the traced program SHARES the
        # dygraph parameter storage — continued eager training is visible
        # to later __call__/save (reference TracedLayer semantics)
        self._param_sources = list(param_sources)
        self._exe = None
        # pre-bound executor plan per feed signature (round-4 VERDICT
        # weak #5: Executor.run's per-call program scan / fetch
        # normalization / cache-key build cost ~17% at launch-bound step
        # sizes; the traced program is frozen, so bind once)
        self._steps = {}
        self._feed_names = [v.name for v in feed_vars]
        self._fetch_names = [v.name for v in fetch_vars]

    def _refresh_params(self):
        for name, vb in self._param_sources:
            if self._scope.get(name) is not vb.value:
                self._scope.set(name, vb.value)

    # ------------------------------------------------------------------
    @staticmethod
    def trace(layer, inputs):
        """Run `layer(*inputs)` once eagerly while recording every op;
        returns (eager outputs, TracedLayer). Inputs must be VarBase (use
        to_variable); control flow is captured AS EXECUTED on these
        example inputs — data-dependent Python branches freeze the taken
        path, exactly like the reference tracer."""
        tracer = _current_tracer()
        if tracer is None:
            raise RuntimeError(
                "TracedLayer.trace must run inside fluid.dygraph.guard()")
        if tracer.capture is not None:
            raise RuntimeError("TracedLayer.trace calls cannot nest")
        for v in inputs:
            if not isinstance(v, VarBase):
                raise TypeError(
                    "TracedLayer.trace inputs must be VarBase "
                    "(fluid.dygraph.to_variable), got %r" % (type(v),))
        tracer.capture = []
        try:
            outs = layer(*inputs)
        finally:
            entries, tracer.capture = tracer.capture, None
        out_list = list(outs) if isinstance(outs, (list, tuple)) else [outs]

        program = framework.Program()
        block = program.global_block()
        scope = Scope()
        var_of = {}  # id(VarBase) -> program Variable
        param_sources = []  # (scope name, VarBase) for live params

        def _var_for(v):
            """Map an eager value to a program Variable, creating inputs/
            params/constants on first sight."""
            if isinstance(v, VarBase):
                key = id(v)
                if key in var_of:
                    return var_of[key]
                if v.persistable:
                    name = v.name or framework.unique_name.generate(
                        "traced_param")
                    pv = block.create_var(
                        name=name, shape=tuple(v.value.shape),
                        dtype=str(v.value.dtype), persistable=True)
                    scope.set(name, v.value)
                    param_sources.append((name, v))
                else:
                    # an eager value born OUTSIDE the traced call (e.g. a
                    # to_variable constant): bake it in as a persistable
                    name = framework.unique_name.generate("traced_const")
                    pv = block.create_var(
                        name=name, shape=tuple(v.value.shape),
                        dtype=str(v.value.dtype), persistable=True)
                    scope.set(name, v.value)
                var_of[key] = pv
                return pv
            arr = np.asarray(v)
            name = framework.unique_name.generate("traced_const")
            pv = block.create_var(name=name, shape=tuple(arr.shape),
                                  dtype=str(arr.dtype), persistable=True)
            scope.set(name, arr)
            return pv

        # the example inputs become feed vars
        feed_vars = []
        for i, v in enumerate(inputs):
            name = "traced_input_%d" % i
            pv = block.create_var(name=name, shape=tuple(v.value.shape),
                                  dtype=str(v.value.dtype), is_data=True)
            var_of[id(v)] = pv
            feed_vars.append(pv)

        for op_type, ins, attrs, vouts in entries:
            prog_ins = {slot: [_var_for(v) for v in vs]
                        for slot, vs in ins.items() if vs}
            prog_outs = {}
            for slot, vs in vouts.items():
                ovs = []
                for v in vs:
                    name = framework.unique_name.generate("traced_var")
                    pv = block.create_var(name=name,
                                          shape=tuple(v.value.shape),
                                          dtype=str(v.value.dtype))
                    var_of[id(v)] = pv
                    ovs.append(pv)
                prog_outs[slot] = ovs
            block.append_op(type=op_type, inputs=prog_ins,
                            outputs=prog_outs, attrs=dict(attrs))

        fetch_vars = []
        for v in out_list:
            if id(v) not in var_of:
                raise RuntimeError(
                    "traced output was not produced by a recorded op — "
                    "return values must flow through layer ops")
            fetch_vars.append(var_of[id(v)])
        return outs, TracedLayer(program, feed_vars, fetch_vars, scope,
                                 param_sources)

    # ------------------------------------------------------------------
    def __call__(self, inputs):
        """Run the captured Program as ONE jitted executor step; returns a
        list of numpy arrays (one per traced output).

        The executor plan is PRE-BOUND: the traced program is frozen at
        trace time, so the compiled step binds directly to (feed
        signature) — no per-call program scan, fetch normalization, or
        strong-cache key construction (Executor.run's generality tax,
        measured at ~17% on launch-bound steps, BASELINE.md dygraph
        row)."""
        from ..executor import _CompiledStep, _feed_signature
        from ..flags import flag

        self._refresh_params()
        feed = {}
        for pv, v in zip(self._feed_vars, inputs):
            feed[pv.name] = v.value if isinstance(v, VarBase) \
                else np.asarray(v)
        key = (_feed_signature(feed), bool(flag("check_nan_inf")))
        step = self._steps.get(key)
        if step is None:
            step = _CompiledStep(self.program, self._feed_names,
                                 self._fetch_names, self._scope)
            self._steps[key] = step
        return [np.asarray(f) for f in step.run(self._scope, feed)]

    # ------------------------------------------------------------------
    def save_inference_model(self, dirname, feed=None, fetch=None):
        """Persist the captured Program + parameters as the standard
        inference artifact (io.save_inference_model), loadable by the
        AnalysisPredictor / serving exporter. `feed`/`fetch` select by
        index into the traced inputs/outputs (reference signature)."""
        from .. import io
        from ..executor import Executor
        from ..core.place import default_place

        feed_vars = (self._feed_vars if feed is None
                     else [self._feed_vars[i] for i in feed])
        fetch_vars = (self._fetch_vars if fetch is None
                      else [self._fetch_vars[i] for i in fetch])
        exe = Executor(default_place())
        self._refresh_params()
        with scope_guard(self._scope):
            io.save_inference_model(
                dirname, [v.name for v in feed_vars], fetch_vars, exe,
                main_program=self.program)
