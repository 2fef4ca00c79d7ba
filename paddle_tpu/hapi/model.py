"""``paddle.Model`` — the Keras-like high-level trainer.

Analog of the reference's ``python/paddle/hapi/model.py:915`` (prepare /
fit:1574 / evaluate / predict, Dynamic+Static adapters at :704/:290).

TPU-native design replaces both adapters with ONE path: the whole train step
— forward, loss, backward, grad clip, optimizer update, buffer (BN stat)
update — is a pure function over (params, opt_state, buffers, rng, lr,
batch) compiled once by XLA. The stateful Layer API feeds it through the
``functional_state`` bridge (nn/layer/layers.py). Dropout keys derive from a
per-step folded PRNG key, so masks vary across steps while the trace stays
static. Loss scaling (fp16) runs inside the step; with bf16 (TPU default)
the scaler is inert.
"""
from __future__ import annotations

import os
import time
from collections import deque
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..framework import program_registry as _registry
from ..framework import random as _random
from ..framework import trace_probe as _probe
from ..framework.io import load as _load, save as _save
from ..framework.monitor import stat_add, stat_get, stat_observe
from ..framework.tensor import Tensor, no_grad_guard
from ..profiler import memory as _memory
from ..profiler import numerics as _numerics
from ..profiler import span as _prof
from ..io import DataLoader, Dataset
from ..metric import Metric
from ..nn.layer.layers import Layer, functional_state
from . import zero as _zero
from .callbacks import config_callbacks

__all__ = ["Model"]


def _to_list(x):
    if x is None:
        return []
    return list(x) if isinstance(x, (list, tuple)) else [x]


def _drop_ledger_keys(keys):
    """weakref.finalize target for a Model's HBM-ledger entries — a
    module function so the finalizer holds no reference to the Model."""
    for k in keys:
        _memory.ledger_drop(k)


class _StaticGraphAdapter:
    """Routes Model.fit/evaluate/predict through the static
    Program/Executor when ``paddle.enable_static()`` is active — the
    analog of the reference's StaticGraphAdapter (hapi/model.py:290),
    which builds ProgramDescs instead of running the dygraph engine.

    The network's forward is captured ONCE into a main Program under
    ``program_guard`` (feeds from the Model's InputSpecs), the loss and
    optimizer are appended, and every train_batch is one Executor.run.
    Eval/predict run a ``for_test`` clone of the same capture.

    Train and eval are captured as SEPARATE programs — the train capture
    records train-mode ops (active dropout, batch-stat BN) and the
    test capture records eval-mode ops, mirroring the reference's
    main/test ProgramDesc pair.

    Known gaps vs the dynamic path (both from the replay being pure over
    build-time constants): BatchNorm running stats do not update across
    static training steps (train-mode normalization itself is exact),
    and dropout masks are frozen at capture — active in the train
    program but identical every step. The reference regenerates both via
    in-graph ops."""

    def __init__(self, model: "Model"):
        self.model = model
        self._built = False

    def _spec_name(self, spec, prefix, i):
        return getattr(spec, "name", None) or f"{prefix}_{i}"

    def _capture(self, program, startup=None, with_optimizer=False):
        from .. import static
        m = self.model
        with static.program_guard(program, startup):
            in_vars = [
                static.data(self._spec_name(s, "input", i),
                            list(s.shape), str(s.dtype))
                for i, s in enumerate(m._inputs)]
            label_vars = [
                static.data(self._spec_name(s, "label", i),
                            list(s.shape), str(s.dtype))
                for i, s in enumerate(m._labels)]
            outputs = m.network(*in_vars)
            outs = outputs if isinstance(outputs, (list, tuple)) \
                else [outputs]
            loss = None
            if m._loss is not None and label_vars:
                loss = m._loss(*outs, *label_vars)
                if with_optimizer and m._optimizer is not None:
                    m._optimizer.minimize(loss)
        return loss, outs

    def _build(self):
        from .. import static
        m = self.model
        if not m._inputs:
            raise ValueError(
                "static-graph Model requires inputs=[InputSpec(...)] at "
                "construction (the reference StaticGraphAdapter contract: "
                "feeds must be declared before the program is built)")
        was_training = m.network.training
        main, startup = static.Program(), static.Program()
        try:
            m.network.train()
            self._loss_var, self._out_vars = self._capture(
                main, startup, with_optimizer=True)
            m.network.eval()
            test = static.Program()
            self._test_loss_var, self._test_out_vars = self._capture(test)
        finally:
            m.network.train() if was_training else m.network.eval()
        self._exe = static.Executor()
        self._exe.run(startup)
        self._main, self._test = main, test
        self._in_names = [self._spec_name(s, "input", i)
                          for i, s in enumerate(m._inputs)]
        self._label_names = [self._spec_name(s, "label", i)
                             for i, s in enumerate(m._labels)]
        self._built = True

    def _feed(self, inputs, labels, need_labels):
        if need_labels and self._label_names and not labels:
            raise ValueError(
                f"this batch must include labels for declared feed(s) "
                f"{self._label_names} (the fetched loss depends on them)")
        arrays = _as_arrays(inputs) + (_as_arrays(labels) if labels else [])
        names = self._in_names + (self._label_names if labels else [])
        if len(arrays) != len(names):
            raise ValueError(
                f"batch has {len(arrays)} arrays but the static program "
                f"declares {len(names)} feeds ({names})")
        return dict(zip(names, arrays))

    def train_batch(self, inputs, labels=None):
        if not self._built:
            self._build()
        if self._loss_var is None:
            raise RuntimeError("no loss/labels declared: static-mode "
                               "training needs labels=[InputSpec] + loss")
        fetches = [self._loss_var] + self._out_vars
        res = self._exe.run(self._main,
                            feed=self._feed(inputs, labels, True),
                            fetch_list=fetches)
        loss, outs = res[0], res[1:]
        metrics = self.model._update_metrics(
            outs, _as_arrays(labels) if labels else [])
        loss = float(np.asarray(loss).ravel()[0])
        return (loss, metrics) if metrics else loss

    def eval_batch(self, inputs, labels=None):
        if not self._built:
            self._build()
        with_loss = self._test_loss_var is not None and bool(labels)
        fetches = ([self._test_loss_var] if with_loss else []) \
            + self._test_out_vars
        res = self._exe.run(self._test,
                            feed=self._feed(inputs, labels, with_loss),
                            fetch_list=fetches)
        if with_loss:
            loss, outs = float(np.asarray(res[0]).ravel()[0]), res[1:]
        else:
            loss, outs = 0.0, res
        metrics = self.model._update_metrics(
            outs, _as_arrays(labels) if labels else [])
        return (loss, metrics) if metrics else loss

    def predict_batch(self, inputs):
        if not self._built:
            self._build()
        res = self._exe.run(self._test, feed=self._feed(inputs, None,
                                                        False),
                            fetch_list=self._test_out_vars)
        return [np.asarray(o) for o in res]


def _as_arrays(batch):
    import jax

    def one(b):
        if isinstance(b, Tensor):
            return b._data
        if isinstance(b, jax.Array):
            return b  # already on device: never round-trip through host
        return np.asarray(b)

    if isinstance(batch, (list, tuple)):
        return [one(b) for b in batch]
    return [one(batch)]


class Model:
    # with metrics attached, the async-fit window holds each step's
    # outputs until the flush; this caps how many batches of outputs can
    # be pinned on device when log_freq is large (sync count stays
    # O(steps / min(log_freq, cap)) — still windowed, never per-step)
    _METRIC_WINDOW = 8
    # numerics audit vectors buffered between flushes are tiny ((6 +
    # groups) f32 each) but one per STEP: with log_freq<=0 (epoch-tail
    # flushes only) an unbounded buffer would pin O(steps-per-epoch)
    # device handles — the one invariant the loss window's O(1)
    # overwrite exists to protect. Ring semantics instead: the NEWEST
    # cap's worth survive to the flush (a NaN propagates, so the tail
    # still trips the sentinel even when the origin step was dropped),
    # drops counted in hapi/audit_window_dropped. Forcing a flush would
    # add host syncs vs numerics-off, breaking the identical-sync-budget
    # contract — dropping is the honest bounded choice.
    _AUDIT_WINDOW = 4096

    def __init__(self, network: Layer, inputs=None, labels=None):
        self.network = network
        self._inputs = _to_list(inputs)
        self._labels = _to_list(labels)
        self._optimizer = None
        self._loss = None
        self._metrics = []
        self._train_step_fn = None
        self._eval_step_fn = None
        self._predict_step_fn = None
        self._params = None       # dict name -> jnp array (device state)
        self._opt_state = None
        self._buffers = None
        self._frozen = None       # stop_gradient param names (static
        #                           split baked into the jitted step)
        self._dirty = False       # functional state newer than network?
        self._step_counter = 0
        self._amp_level = "O0"
        self._amp_dtype = "bfloat16"
        self._static_adapter = None
        self.stop_training = False
        # achieved-FLOP/s accounting for the async fit window: FLOPs of
        # the step programs actually DISPATCHED since the last flush +
        # the window's start stamp (hapi/flops_per_sec, hapi/mfu — see
        # _observe_compute). Summing per dispatch — not steps × the
        # record's latest-compile figure — keeps a partial last batch
        # (its own smaller program) from mis-billing full-batch steps.
        self._flush_flops = 0.0
        self._flush_steps = 0
        self._flush_t0 = None
        # numerics health (profiler/numerics.py): when fit(numerics=)
        # is not 'off', the device-side audit is COMPILED INTO the
        # donated train step (one extra small output + a traced inject
        # scalar, zero extra programs) and its vectors ride the flush
        # window — fetched only behind the window's one blocking loss
        # fetch, so hapi/host_sync is IDENTICAL with numerics on or off
        self._numerics_mode = "off"   # policy applied host-side at flush
        self._audit_enabled = False   # audit baked into the built step?
        self._audit_layout = None     # layer-group schema of the vector
        # [(global step, device vector, layout)] ring — see _AUDIT_WINDOW
        self._audit_window = deque(maxlen=self._AUDIT_WINDOW)
        self._audit_collect = False   # only fit() windows collect
        self._numerics_recorder = None
        self._retrace_mark = 0.0      # dispatch/retrace_cause watermark
        # test hook: scale the loss by +inf when _step_counter hits this
        # value (traced scalar — same compiled program) so the sentinel
        # path is testable without NaN-crafted data
        self._numerics_inject_inf_at = None
        # ZeRO-sharded weight update (hapi/zero.py, fit(zero=1)): the
        # optimizer state lives dp-sharded as flat f32 stripes and the
        # donated step runs reduce-scatter -> shard-local update ->
        # all-gather inside a shard_map over _zero_mesh. _zero_layout
        # is the padding map; _zero_t0 keeps per-param birth steps
        # host-side (the flat analog of the "_t0" slot marker, baked
        # into the step as a constant — a change always rides a
        # frozen-set re-trace). _grad_comm picks the gradient-exchange
        # precision ('fp32' exact | 'int8' EQuARX-style quantized).
        self._zero_stage = 0
        self._grad_comm = "fp32"
        self._zero_mesh = None
        self._zero_layout = None
        self._zero_t0 = {}

    def _static(self):
        """The StaticGraphAdapter when ``paddle.enable_static()`` is on
        (mode is sampled per call, like the reference's _run_backend)."""
        from ..static import in_dynamic_mode
        if in_dynamic_mode():
            return None
        if self._static_adapter is None:
            self._static_adapter = _StaticGraphAdapter(self)
        return self._static_adapter

    # -- preparation --------------------------------------------------------
    def prepare(self, optimizer=None, loss=None, metrics=None,
                amp_configs=None):
        self._optimizer = optimizer
        self._loss = loss
        self._metrics = _to_list(metrics)
        for m in self._metrics:
            if not isinstance(m, Metric):
                raise TypeError(f"metrics must be paddle.metric.Metric, "
                                f"got {type(m)}")
        if amp_configs:
            if isinstance(amp_configs, str):
                self._amp_level = amp_configs
            else:
                self._amp_level = amp_configs.get("level", "O1")
                self._amp_dtype = amp_configs.get("dtype", "bfloat16")
            if self._amp_level == "O2":
                from ..amp import decorate
                decorate(self.network, level="O2", dtype=self._amp_dtype)
        return self

    def _sync_state_from_network(self):
        # snapshot the (name, Tensor) bindings once per sync: the
        # per-step rebind must not pay a recursive module walk
        self._bind_params = list(self.network.named_parameters())
        self._bind_buffers = list(self.network.named_buffers())
        net_params = {n: p._data for n, p in self._bind_params}
        net_buffers = {n: b._data for n, b in self._bind_buffers}
        if self._params is not None:
            # after a donated step the network Tensors hold stale
            # (deleted) handles until _sync_state_to_network runs; for
            # those the functional state IS the current value. A valid
            # network array — user assignment, set_state_dict — still
            # wins, preserving "the network is the API surface".
            def _undeleted(tree, current):
                return {
                    k: current[k]
                    if (k in current and hasattr(v, "is_deleted")
                        and v.is_deleted()) else v
                    for k, v in tree.items()}
            net_params = _undeleted(net_params, self._params)
            net_buffers = _undeleted(net_buffers, self._buffers or {})
        self._params = net_params
        self._buffers = net_buffers
        # frozen set = stop_gradient params. The jitted step bakes it in
        # (static trainable/frozen split), so a change — progressive
        # unfreezing between fits — forces a re-trace and reconciles the
        # optimizer state: surviving moments are kept, newly-trainable
        # params start from zeroed slots, newly-frozen ones are dropped.
        frozen = {name for name, p in self._bind_params
                  if p.stop_gradient}
        # sharded opt state (fit(zero=1)) converts back to the named
        # layout whenever the code below must reconcile it per param —
        # a frozen-set flip, a zero->replicated switch, or a layout no
        # longer matching the trainable tree; otherwise the stripes
        # stay on device untouched (re-fits never round-trip state)
        if self._opt_state is not None and \
                _zero.is_sharded_state(self._opt_state):
            stale = (self._zero_layout is None
                     or not self._zero_layout.compatible_with(
                         {k: v for k, v in self._params.items()
                          if k not in frozen}))
            if not self._zero_stage or frozen != self._frozen or stale:
                self._opt_state = self._zero_gather_named()
        if self._frozen is not None and frozen != self._frozen:
            # invalidate the step; when the rebuilt step re-traces, the
            # hapi/train_step probe site diffs its static frozen_set
            # component and classifies the retrace cause as frozen_set
            # (framework/trace_probe.py) — the recompile-churn analysis
            # pass warns on a flapping set
            self._train_step_fn = None
            if self._optimizer is not None and self._opt_state is not None:
                old = self._opt_state
                trainable = {k: v for k, v in self._params.items()
                             if k not in frozen}
                new_state = self._optimizer.init_state(trainable)
                for name, slots in new_state["slots"].items():
                    old_slots = old["slots"].get(name)
                    if old_slots is None:
                        # newly-trainable param: zeroed moments — record
                        # its birth step so Adam-style bias correction
                        # runs from this param's own t=0 (see "_t0" in
                        # Optimizer.apply_gradients) instead of
                        # mis-scaling against the global step history.
                        # `+ 0` forces a DISTINCT buffer: sharing the
                        # step array across donated slots is a
                        # donate-the-same-buffer-twice XLA error
                        slots["_t0"] = old["step"] + 0
                        continue
                    for sname, arr in old_slots.items():
                        if sname in slots and \
                                arr.shape == slots[sname].shape:
                            slots[sname] = arr
                        elif sname == "_t0":
                            slots[sname] = arr  # keep the birth marker
                new_state["step"] = old["step"]
                self._opt_state = new_state
        self._frozen = frozen
        if self._optimizer is not None and self._opt_state is not None \
                and int(getattr(self._optimizer, "_step_count", 0)) > \
                int(self._opt_state["step"]):
            # eager opt.step() ran since the last mirror: the
            # optimizer's slot store is the newer state — rebuild the
            # functional state from it (the overlay below reads both key
            # namespaces) instead of resuming the stale snapshot and
            # silently discarding the eager progress
            self._opt_state = None
        if self._optimizer is not None and self._opt_state is None:
            self._opt_state = self._optimizer.init_state(
                {k: v for k, v in self._params.items() if k not in frozen})
            # overlay restored slots (optimizer.set_state_dict via
            # Model.load, or prior eager opt.step() training) so existing
            # moments survive the functional re-init. Two key namespaces
            # exist: hapi checkpoints use structural tree names (stable
            # across processes/instances), the eager optimizer keys by
            # Parameter.name (process-global counters) — accept either.
            restored = getattr(self._optimizer, "_slots", {})
            eager_name = {n: p.name
                          for n, p in self.network.named_parameters()}
            any_restored = False
            for name, slots in self._opt_state["slots"].items():
                src = restored.get(name) or \
                    restored.get(eager_name.get(name), {})
                for sname in slots:
                    arr = src.get(sname)
                    if arr is not None and arr.shape == slots[sname].shape:
                        slots[sname] = jnp.asarray(arr, slots[sname].dtype)
                        any_restored = True
                if "_t0" in src:  # birth-step marker rides along
                    slots["_t0"] = jnp.asarray(src["_t0"], jnp.int32) + 0
            # carry the step count only when moments came with it (or the
            # optimizer keeps none, e.g. SGD) — step>0 over zeroed Adam
            # moments would silently mis-scale the bias correction
            step = int(getattr(self._optimizer, "_step_count", 0))
            if step and (any_restored or not self._optimizer._slot_names):
                self._opt_state["step"] = jnp.asarray(step, jnp.int32)
        if self._zero_stage and self._optimizer is not None and \
                self._opt_state is not None and \
                not _zero.is_sharded_state(self._opt_state):
            self._arm_zero()

    def _zero_validate(self):
        """fit(zero=1) compatibility gate — reject configurations the
        flat stripe update cannot express, with the fix in the
        message, instead of training silently-wrong."""
        opt = self._optimizer
        if not getattr(opt, "_flat_rule_supported", True):
            raise ValueError(
                f"fit(zero=1) cannot shard {type(opt).__name__}: its "
                f"update rule has per-parameter semantics a flat stripe "
                f"cannot express (e.g. Lamb's trust ratio); use the "
                f"replicated step (zero=0) or an elementwise optimizer")
        if getattr(opt, "_multi_precision", False):
            raise ValueError(
                "fit(zero=1) does not keep fp32 master-weight slots "
                "(the flat update already runs in f32 over the cast-up "
                "params); disable multi_precision or use zero=0")
        clip = getattr(opt, "_grad_clip", None)
        if clip is not None:
            from ..nn.clip import ClipGradByGlobalNorm, ClipGradByValue
            if not isinstance(clip, (ClipGradByGlobalNorm,
                                     ClipGradByValue)):
                raise ValueError(
                    f"fit(zero=1) supports ClipGradByGlobalNorm (cross-"
                    f"shard psum norm) and ClipGradByValue (elementwise) "
                    f"— {type(clip).__name__} clips per TENSOR, which a "
                    f"flat stripe cannot see; use zero=0")

    def _arm_zero(self):
        """Adopt the ZeRO shard layout: resolve the dp mesh, build the
        padding map over the trainable tree, stripe the NAMED opt state
        onto the mesh, and land params/buffers replicated so the
        compiled step's input shardings are stable from the first
        dispatch. The per-param ``_t0`` birth markers move into a host
        dict (``_zero_t0``) — they only change on frozen-set flips,
        which re-trace anyway, so the step bakes them as a constant."""
        self._zero_validate()
        mesh = _zero.resolve_mesh()
        frozen = frozenset(self._frozen or ())
        trainable = {k: v for k, v in self._params.items()
                     if k not in frozen}
        layout = _zero.FlatLayout.build(
            trainable, int(np.prod(mesh.devices.shape)))
        named = self._opt_state
        self._zero_t0 = {
            name: int(np.asarray(slots["_t0"]))
            for name, slots in named.get("slots", {}).items()
            if "_t0" in slots}
        self._opt_state = _zero.shard_opt_state(
            named, layout, mesh, self._optimizer._slot_names)
        self._zero_mesh, self._zero_layout = mesh, layout
        rep = _zero.replicated_sharding(mesh)
        self._params = {k: jax.device_put(v, rep)
                        for k, v in self._params.items()}
        self._buffers = {k: jax.device_put(v, rep)
                         for k, v in (self._buffers or {}).items()}
        self._rebind_network_state()

    def _zero_gather_named(self):
        """Sharded opt state -> the named {"step", "slots"} layout
        (host gather; fit boundaries only), with the ``_t0`` markers
        re-attached from the host map."""
        named = _zero.gather_opt_state(
            self._opt_state, self._zero_layout,
            self._optimizer._slot_names)
        for name, t0 in self._zero_t0.items():
            if name in named["slots"]:
                named["slots"][name]["_t0"] = jnp.asarray(t0, jnp.int32)
        return named

    def _rebind_network_state(self):
        """Point the network's Tensors at the CURRENT functional state.

        Pure Python reference assignment — no device work, no host sync,
        no module walk (bindings snapshotted in _sync_state_from_network)
        — so the donated train step can run it every dispatch: user code
        reading ``net.some.weight`` between steps sees live post-step
        arrays instead of the donated (deleted) pre-step buffers."""
        if self._params is None:
            return
        binds = getattr(self, "_bind_params", None)
        if binds is None:
            binds = list(self.network.named_parameters())
        for name, p in binds:
            if name in self._params:
                p._data = self._params[name]
        bbinds = getattr(self, "_bind_buffers", None)
        if bbinds is None:
            bbinds = list(self.network.named_buffers())
        for name, b in bbinds:
            if name in self._buffers:
                b._data = self._buffers[name]

    def _sync_state_to_network(self):
        # freshness guard: only mirror when the functional state has
        # advanced since the last sync (_dirty set per dispatch) —
        # unconditional mirroring would roll back eager training done
        # AFTER fit() (p._data and optimizer slots reverting to the
        # fit-era snapshot on a mere model.parameters() call)
        if not self._dirty:
            return
        self._rebind_network_state()
        # mirror the functional opt state back into the optimizer's eager
        # slot store so state_dict()/save() reflect training done through
        # the jitted (donated) step — without this, moments trained in
        # fit() were silently dropped from the .pdopt checkpoint
        if self._optimizer is not None and self._opt_state is not None:
            # a dp-sharded opt state (fit(zero=1)) gathers ON DEMAND
            # here — state_dict()/save() and the eager bridge always
            # see the named layout, so a zero=1 checkpoint is byte-for-
            # byte the replicated format (and restores into either)
            state = self._zero_gather_named() \
                if _zero.is_sharded_state(self._opt_state) \
                else self._opt_state
            self._optimizer._slots = {
                name: dict(slots)
                for name, slots in state["slots"].items()}
            self._optimizer._step_count = int(state["step"])
            # bridge for a later eager opt.step(): Parameter.name ->
            # tree name, so _ensure_slots migrates these entries instead
            # of restarting from zeros (see Optimizer._ensure_slots)
            binds = getattr(self, "_bind_params", None) or \
                list(self.network.named_parameters())
            self._optimizer._slot_aliases = {p.name: n for n, p in binds}
        self._dirty = False

    def _loss_tensors(self, outputs, labels):
        if self._loss is None:
            raise RuntimeError(
                "no loss configured: call model.prepare(optimizer, loss) "
                "before fit/train_batch")
        outs = outputs if isinstance(outputs, (list, tuple)) else [outputs]
        loss = self._loss(*outs, *labels)
        return loss

    def _maybe_amp(self):
        from ..amp import auto_cast
        import contextlib
        if self._amp_level in ("O1", "O2"):
            return auto_cast(level=self._amp_level, dtype=self._amp_dtype)
        return contextlib.nullcontext()

    @_prof.record("hapi/build_train_step", "hapi")
    def _build_train_step(self):
        if self._zero_stage:
            return self._build_zero_train_step()
        self._pallas_gate()
        net, opt = self.network, self._optimizer
        clip = getattr(opt, "_grad_clip", None)
        # static split, baked into the trace: frozen (stop_gradient)
        # params are threaded through untouched — no gradient computed,
        # no optimizer slots, output aliases the donated input — which
        # is both the dygraph freezing contract (the old functional step
        # silently trained frozen params) and free under donation
        frozen = frozenset(self._frozen or ())
        # numerics audit (profiler/numerics.py): fused into THIS step's
        # trace when armed — per-step finite bitmask, grad/param/update
        # norms and per-layer-group nonfinite counts as one small f32
        # output next to the loss. 'record'/'warn'/'halt' share the
        # program (policy is host-side at the flush window); only
        # off<->on changes the trace.
        audit_on = self._numerics_mode != "off"
        self._audit_enabled = audit_on
        layout = None
        if audit_on:
            layout = _numerics.AuditLayout.build(
                [k for k in (self._params or {}) if k not in frozen])
        self._audit_layout = layout
        from ..nn.clip import ClipGradByGlobalNorm
        reuse_clip_norm = audit_on and isinstance(clip,
                                                  ClipGradByGlobalNorm)

        # per-INSTANCE site: another Model (even of the same class) must
        # not diff this one's signatures into phantom structure/shape
        # retraces — its first compile is not this model's churn. Held
        # on the Model so rebuilds keep ONE site (and keep counting)
        # even past the trace_probe registry cap.
        probe_site = getattr(self, "_probe_site", None)
        if probe_site is None:
            Model._probe_seq = getattr(Model, "_probe_seq", 0) + 1
            probe_site = self._probe_site = _probe.site(
                f"hapi/train_step[{type(net).__name__}"
                f"#{Model._probe_seq}]")

        def _step(params, opt_state, buffers, key, lr, inject, n_inputs,
                  arrays):
            # body runs only while jax TRACES a new signature, so this
            # classifies every donated-step retrace (shape vs dtype vs
            # frozen-set) into dispatch/retrace_cause at trace time —
            # zero steady-state cost (framework/trace_probe.py)
            probe_site.record(
                _probe.sig_of(list(params.values())
                              + list(buffers.values()) + list(arrays)),
                {"n_inputs": n_inputs, "frozen_set": tuple(sorted(frozen))})
            inputs = arrays[:n_inputs]
            label_arrays = arrays[n_inputs:]
            froz_p = {k: v for k, v in params.items() if k in frozen}
            train_p = {k: v for k, v in params.items() if k not in frozen}

            def loss_of(p):
                with _random.rng_guard(key), self._maybe_amp():
                    with functional_state(net, {**p, **froz_p},
                                          buffers) as st:
                        with no_grad_guard():
                            ins = [Tensor(a, stop_gradient=True)
                                   for a in inputs]
                            outputs = net(*ins)
                            labels = [Tensor(a) for a in label_arrays]
                            loss = self._loss_tensors(outputs, labels)
                    new_buffers = st["updated_buffers"]
                outs = outputs if isinstance(outputs, (list, tuple)) \
                    else [outputs]
                loss_data = loss._data.astype(jnp.float32)
                if audit_on:
                    # traced inject scalar (1.0 in production): the
                    # numerics test hook scales the loss to +inf at a
                    # chosen step through the SAME compiled program
                    loss_data = loss_data * inject
                return loss_data, ([o._data for o in outs], new_buffers)

            (loss_val, (outs, new_buffers)), grads = jax.value_and_grad(
                loss_of, has_aux=True)(train_p)
            raw_grads = grads
            pre_norm = post_norm = None
            if clip is not None:
                pairs_in = [(train_p[k], g) for k, g in grads.items()]
                if reuse_clip_norm:
                    # the clip already reduces the whole gradient tree
                    # to its global norm — the audit reads that value
                    # instead of paying the reduction twice. min(norm,
                    # clip) IS the exact clipped norm here: the leaves
                    # are plain jnp arrays, so clip_with_norm's eager
                    # Parameter.need_clip exemption never fires and
                    # every grad scales by clip/max(norm, clip)
                    pairs, pre_norm = clip.clip_with_norm(pairs_in)
                    post_norm = jnp.minimum(
                        pre_norm, jnp.float32(clip.clip_norm))
                else:
                    pairs = clip(pairs_in)
                grads = {k: g for (k, (_, g)) in zip(grads.keys(), pairs)}
                if audit_on and post_norm is None:
                    # per-tensor/value clips have no global-norm to
                    # reuse: reduce the CLIPPED grads so the audit's
                    # clip ratio stays honest (reporting 1.0 while a
                    # value clip was biting would hide exactly the
                    # saturation the telemetry exists to expose)
                    post_norm = _numerics.global_grad_norm(grads)
            new_train, new_opt_state = opt.apply_gradients(
                train_p, grads, opt_state, lr)
            new_params = dict(params)
            new_params.update(new_train)
            if audit_on:
                audit = _numerics.build_audit(
                    loss_val, raw_grads, train_p, new_train, layout,
                    grad_norm=pre_norm, clipped_norm=post_norm)
                return (new_params, new_opt_state, new_buffers, loss_val,
                        outs, audit)
            return new_params, new_opt_state, new_buffers, loss_val, outs

        if audit_on:
            def train_step(params, opt_state, buffers, key, lr, inject,
                           n_inputs, *arrays):
                return _step(params, opt_state, buffers, key, lr, inject,
                             n_inputs, arrays)
            static_argnums = (6,)
        else:
            def train_step(params, opt_state, buffers, key, lr, n_inputs,
                           *arrays):
                return _step(params, opt_state, buffers, key, lr, None,
                             n_inputs, arrays)
            static_argnums = (5,)

        # donate params/opt_state/buffers: every output leaf has a
        # same-shape/dtype donated input, so XLA aliases the update
        # in-place instead of allocating a second copy of the whole train
        # state per step — halving train-state HBM residency (the sharded
        # weight-update argument of arXiv 2004.13336, applied to
        # single-chip aliasing). The OLD buffers are deleted the moment
        # the step is dispatched: _dispatch_train_step rebinds
        # self._params/_opt_state/_buffers AND the network's Tensors to
        # the results (reference assignment, no sync), so nothing may —
        # or can accidentally — touch the donated arrays afterwards;
        # a raw pre-step ._data capture raises jax's "Array has been
        # deleted", never silent garbage.
        #
        # The step is an AOT program-registry site (same jit semantics —
        # static n_inputs, donated train state — but the executable is
        # compiled explicitly ONCE per signature): compile wall-ms lands
        # in compile/ms, and the program's XLA cost analysis
        # (FLOPs/bytes) is what _observe_compute turns into
        # hapi/flops_per_sec and hapi/mfu at every flush window. With
        # numerics armed the audit is part of THIS program — never a
        # second compile per signature (bench.py --dry-run asserts the
        # registry compile/count stays flat across a warm re-fit).
        self._train_step_fn = _registry.aot_site(
            probe_site.name, train_step, static_argnums=static_argnums,
            donate_argnums=(0, 1, 2))

    def _build_zero_train_step(self):
        """The ZeRO-sharded twin of ``_build_train_step`` (fit(zero=1),
        hapi/zero.py; arXiv 2004.13336): ONE donated compiled program
        per signature — same argument/static/donation discipline as the
        replicated step — whose body runs inside a ``shard_map`` over
        the dp mesh axis. Per replica: forward+backward on the LOCAL
        batch slice against replicated params, reduce-scatter the flat
        gradient (f32 exact, or the EQuARX-style int8 exchange under
        ``grad_comm='int8'``), shard-local optimizer rule over this
        replica's 1/dp stripe of params and opt state, all-gather the
        updated stripes back into the named tree. Losses/outs leave the
        map as the full-batch mean / the batch-concatenated outputs, so
        everything downstream (flush window, metrics, callbacks) is
        layout-blind. The numerics audit, when armed, is the
        cross-shard variant (build_audit_flat) over the POST-exchange
        dequantized gradient — quantization corruption trips the
        sentinel at the exact step with per-layer-group blame."""
        self._pallas_gate()
        self._zero_validate()
        net, opt = self.network, self._optimizer
        clip = getattr(opt, "_grad_clip", None)
        frozen = frozenset(self._frozen or ())
        mesh, layout = self._zero_mesh, self._zero_layout
        if mesh is None or layout is None:
            raise RuntimeError(
                "zero train step built before the shard layout was "
                "armed — _sync_state_from_network must run first")
        AXIS = _zero.AXIS
        dp, stripe = layout.dp, layout.stripe
        grad_comm = self._grad_comm
        from jax.sharding import PartitionSpec as P
        from ..distributed import collective as _collective
        from ..nn.clip import ClipGradByGlobalNorm, ClipGradByValue
        is_global_clip = isinstance(clip, ClipGradByGlobalNorm)

        audit_on = self._numerics_mode != "off"
        self._audit_enabled = audit_on
        alayout = None
        group_ids = None
        if audit_on:
            alayout = _numerics.AuditLayout.build(
                [k for k in (self._params or {}) if k not in frozen])
            group_ids = layout.group_ids(alayout)
        self._audit_layout = alayout
        # per-param predicates baked as flat constants (they can only
        # change alongside a re-trace): AdamW's decoupled-decay
        # exclusion mask and the _t0 birth-step vector
        decay_mask = None
        if getattr(opt, "_apply_decay_param_fun", None) is not None:
            decay_mask = layout.mask_from(
                [n for n in layout.names if opt._wd_enabled(n)])
        t0_vec = layout.t0_vector(self._zero_t0) if self._zero_t0 \
            else None

        probe_site = getattr(self, "_probe_site", None)
        if probe_site is None:
            Model._probe_seq = getattr(Model, "_probe_seq", 0) + 1
            probe_site = self._probe_site = _probe.site(
                f"hapi/train_step[{type(net).__name__}"
                f"#{Model._probe_seq}]")

        def _stripe_of(full, idx):
            return jax.lax.dynamic_slice(jnp.asarray(full),
                                         (idx * stripe,), (stripe,))

        def _step(params, opt_state, buffers, key, lr, inject, n_inputs,
                  arrays):
            # BODY RUNS INSIDE shard_map: params/buffers/key/lr are
            # replicated per-device views, opt_state["flat"] arrays are
            # this replica's [stripe] slices, arrays are the local
            # batch shard (axis 0 split dp ways)
            idx = jax.lax.axis_index(AXIS)
            rkey = jax.random.fold_in(key, idx)  # per-replica dropout
            inputs = arrays[:n_inputs]
            label_arrays = arrays[n_inputs:]
            froz_p = {k: v for k, v in params.items() if k in frozen}
            train_p = {k: v for k, v in params.items()
                       if k not in frozen}

            def loss_of(p):
                with _random.rng_guard(rkey), self._maybe_amp():
                    with functional_state(net, {**p, **froz_p},
                                          buffers) as st:
                        with no_grad_guard():
                            ins = [Tensor(a, stop_gradient=True)
                                   for a in inputs]
                            outputs = net(*ins)
                            labels = [Tensor(a) for a in label_arrays]
                            loss = self._loss_tensors(outputs, labels)
                    new_buffers = st["updated_buffers"]
                outs = outputs if isinstance(outputs, (list, tuple)) \
                    else [outputs]
                loss_data = loss._data.astype(jnp.float32)
                if audit_on:
                    loss_data = loss_data * inject
                return loss_data, ([o._data for o in outs], new_buffers)

            (loss_val, (outs, new_buffers)), grads = jax.value_and_grad(
                loss_of, has_aux=True)(train_p)
            # gradient exchange: each replica ends holding the summed
            # 1/dp stripe it owns; /dp turns per-slice-mean grads into
            # the exact full-batch mean (equal slices)
            flat_g = layout.flatten(grads)
            if grad_comm == "int8":
                g_sum = _zero.quantized_reduce_scatter(
                    flat_g, AXIS, dp, stripe, layout.chunk)
            else:
                g_sum = _collective.reduce_scatter_in_axis(flat_g, AXIS)
            g_stripe = g_sum / jnp.float32(dp)
            raw_stripe = g_stripe  # post-exchange, dequantized, pre-clip
            pre_norm = post_norm = None
            if clip is not None:
                if is_global_clip:
                    # the global norm needs the cross-shard psum term —
                    # a local-stripe norm under-clips by ~sqrt(dp)
                    pre_norm = jnp.sqrt(jax.lax.psum(
                        jnp.sum(jnp.square(g_stripe)), AXIS))
                    cn = jnp.float32(clip.clip_norm)
                    g_stripe = g_stripe * (cn / jnp.maximum(pre_norm,
                                                            cn))
                    post_norm = jnp.minimum(pre_norm, cn)
                else:  # ClipGradByValue: elementwise, stripe-local
                    g_stripe = jnp.clip(g_stripe, clip.min, clip.max)
                    if audit_on:
                        post_norm = jnp.sqrt(jax.lax.psum(
                            jnp.sum(jnp.square(g_stripe)), AXIS))
            flat_p = layout.flatten(train_p)
            p_stripe = jax.lax.dynamic_slice(flat_p, (idx * stripe,),
                                             (stripe,))
            step_no = opt_state["step"] + 1
            eff = step_no if t0_vec is None \
                else step_no - _stripe_of(t0_vec, idx)
            mstripe = None if decay_mask is None \
                else _stripe_of(decay_mask, idx)
            new_stripe, new_slots = opt.flat_rule(
                p_stripe, g_stripe, dict(opt_state["flat"]), lr, eff,
                decay_mask=mstripe)
            new_flat = _collective.all_gather_in_axis(
                new_stripe.astype(jnp.float32), AXIS, tiled=True,
                axis=0)
            new_train = layout.unflatten(new_flat, train_p)
            new_params = dict(params)
            new_params.update(new_train)
            new_buffers = _zero.replicate_buffers(new_buffers, AXIS, dp)
            loss_full = jax.lax.pmean(loss_val, AXIS)
            new_state = {"step": step_no, "flat": new_slots}
            if audit_on:
                audit = _numerics.build_audit_flat(
                    loss_full, raw_stripe, p_stripe, new_stripe,
                    _stripe_of(group_ids, idx), alayout, AXIS,
                    grad_norm=pre_norm, clipped_norm=post_norm)
                return (new_params, new_state, new_buffers, loss_full,
                        outs, audit)
            return new_params, new_state, new_buffers, loss_full, outs

        opt_spec = {"step": P(), "flat": P(AXIS)}
        base_in = (P(), opt_spec, P(), P(), P())
        base_out = (P(), opt_spec, P(), P(), P(AXIS))

        # check_vma=False (the shim's name for check_rep): the rep
        # checker cannot statically prove the all-gathered params /
        # pmean'd loss replicated, and the out_specs above ARE the
        # contract (every P() output is produced by an explicit
        # psum/pmean/all_gather)
        if audit_on:
            def train_step(params, opt_state, buffers, key, lr, inject,
                           n_inputs, *arrays):
                probe_site.record(
                    _probe.sig_of(list(params.values())
                                  + list(buffers.values())
                                  + list(arrays)),
                    {"n_inputs": n_inputs,
                     "frozen_set": tuple(sorted(frozen)),
                     "zero": (1, dp, grad_comm)})
                sm = jax.shard_map(
                    lambda p, o, b, k, l, i, arrs: _step(
                        p, o, b, k, l, i, n_inputs, arrs),
                    mesh=mesh, in_specs=base_in + (P(), P(AXIS)),
                    out_specs=base_out + (P(),), check_vma=False)
                return sm(params, opt_state, buffers, key, lr, inject,
                          tuple(arrays))
            static_argnums = (6,)
        else:
            def train_step(params, opt_state, buffers, key, lr,
                           n_inputs, *arrays):
                probe_site.record(
                    _probe.sig_of(list(params.values())
                                  + list(buffers.values())
                                  + list(arrays)),
                    {"n_inputs": n_inputs,
                     "frozen_set": tuple(sorted(frozen)),
                     "zero": (1, dp, grad_comm)})
                sm = jax.shard_map(
                    lambda p, o, b, k, l, arrs: _step(
                        p, o, b, k, l, None, n_inputs, arrs),
                    mesh=mesh, in_specs=base_in + (P(AXIS),),
                    out_specs=base_out, check_vma=False)
                return sm(params, opt_state, buffers, key, lr,
                          tuple(arrays))
            static_argnums = (5,)

        # same donation contract as the replicated step: every donated
        # leaf (params replicated, opt stripes dp-sharded, buffers) has
        # a same-aval same-sharding output to alias — the
        # donation-safety pass stays the standing guard, now through
        # the shard_map eqn
        self._train_step_fn = _registry.aot_site(
            probe_site.name, train_step, static_argnums=static_argnums,
            donate_argnums=(0, 1, 2))

    def _analysis_loss_fn(self, ins, lbs):
        """Loss-of-trainable-params closure mirroring _build_train_step's
        ``loss_of`` — the analysis layer (paddle_tpu/analysis) traces
        ``jax.grad`` of this for the dead/frozen-grad pass. Kept here so
        the functional_state/amp/rng plumbing has ONE owner."""
        import jax
        net = self.network
        frozen = frozenset(self._frozen or ())
        params, buffers = self._params, self._buffers
        froz_p = {k: v for k, v in params.items() if k in frozen}
        train_p = {k: v for k, v in params.items() if k not in frozen}
        key = jax.random.key(0)

        def loss_fn(p):
            with _random.rng_guard(key), self._maybe_amp():
                with functional_state(net, {**p, **froz_p}, buffers):
                    with no_grad_guard():
                        tins = [Tensor(a, stop_gradient=True)
                                for a in ins]
                        outputs = net(*tins)
                        labels = [Tensor(a) for a in lbs]
                        loss = self._loss_tensors(outputs, labels)
            return loss._data.astype(jnp.float32)

        return loss_fn, train_p

    def _run_analysis(self, inputs, labels, mode):
        """fit()'s pre-flight: lint the built train step on the first
        batch. 'warn' logs the findings table; 'error' additionally
        raises AnalysisError on error-severity findings. Analyzer
        crashes (not findings) never kill training.

        Also reports the step's donation-aware ``static_peak_bytes``
        (the static-memory pass figure, ISSUE 18) — one log line before
        any compile, plus the ``analysis/train_step_peak_bytes`` gauge —
        so an over-HBM train step is visible from the plan, not from an
        XLA OOM minutes later. Donation misses surface through the same
        findings table (donation-miss pass warnings)."""
        from .. import analysis
        try:
            report = analysis.analyze_model(self, inputs, labels)
        except Exception as e:  # pragma: no cover - analyzer robustness
            import warnings
            warnings.warn(f"static analysis pre-flight failed "
                          f"({type(e).__name__}: {e}); continuing fit",
                          RuntimeWarning)
            return None
        for f in report.findings:
            if f.pass_id == "static-memory" and f.data:
                peak = f.data.get("static_peak_bytes")
                if peak is not None:
                    import sys
                    from ..framework.monitor import stat_observe
                    stat_observe("analysis/train_step_peak_bytes", peak)
                    print(f"[analysis] train step static peak: "
                          f"{peak:,} B ({peak / (1 << 20):.1f} MiB, "
                          f"donation-aware; pre-compile estimate)",
                          file=sys.stderr)
                break
        return analysis.apply_mode(report, mode, "the train step")

    def _build_eval_step(self):
        net = self.network

        def eval_step(params, buffers, key, n_inputs, *arrays):
            inputs = arrays[:n_inputs]
            label_arrays = arrays[n_inputs:]
            with _random.rng_guard(key), self._maybe_amp():
                with functional_state(net, params, buffers):
                    with no_grad_guard():
                        ins = [Tensor(a, stop_gradient=True)
                               for a in inputs]
                        outputs = net(*ins)
                        outs = outputs if isinstance(outputs, (list, tuple))\
                            else [outputs]
                        if self._loss is not None and label_arrays:
                            labels = [Tensor(a) for a in label_arrays]
                            loss = self._loss_tensors(outputs, labels)._data
                        else:
                            loss = jnp.zeros((), jnp.float32)
            return loss, [o._data for o in outs]

        # no donation here: eval/predict REUSE params and buffers across
        # batches (the step returns neither), so donating them would
        # delete live state after the first batch. Registry site like
        # the train step (static n_inputs at position 3).
        self._eval_step_fn = _registry.aot_site(
            "hapi/eval_step", eval_step, static_argnums=(3,))

    # -- single-batch APIs (reference train_batch/eval_batch/predict_batch) -
    def _pallas_gate(self):
        # same smoke gate as ParallelEngine._build: a Pallas kernel that
        # cannot lower on this chip raises PallasSmokeError naming it
        from ..ops import pallas_smoke
        pallas_smoke.ensure()

    def _dispatch_train_step(self, ins, lbs):
        """Dispatch ONE donated jitted step and rebind the train state.

        Returns (loss, outs) as device values without any host sync —
        the donation contract lives here: the previous
        params/opt_state/buffers are consumed by the call, so they are
        rebound to the step's results in the same statement and the old
        handles are never touched again."""
        self._step_counter += 1
        if self._flush_t0 is None:
            self._flush_t0 = time.perf_counter()
        self._flush_steps += 1
        key = jax.random.fold_in(jax.random.key(0), self._step_counter)
        lr = jnp.asarray(self._optimizer.get_lr(), jnp.float32)
        if self._audit_enabled:
            inj = self._numerics_inject_inf_at
            inject = np.float32(np.inf) if (
                inj is not None and self._step_counter == inj) \
                else np.float32(1.0)
            (self._params, self._opt_state, self._buffers, loss, outs,
             audit) = self._train_step_fn(
                self._params, self._opt_state, self._buffers, key, lr,
                inject, len(ins), *ins, *lbs)
            if self._audit_collect:
                # tiny device vector per step ((6 + groups) f32), held
                # until the window flush fetches it behind the loss;
                # the layout rides along so a mid-epoch step rebuild
                # (frozen-set flip) can never decode old vectors
                # against a new group schema
                w = self._audit_window
                if w.maxlen is not None and len(w) == w.maxlen:
                    stat_add("hapi/audit_window_dropped")
                w.append((self._step_counter, audit, self._audit_layout))
        else:
            (self._params, self._opt_state, self._buffers, loss,
             outs) = self._train_step_fn(
                self._params, self._opt_state, self._buffers, key, lr,
                len(ins), *ins, *lbs)
        self._flush_flops += getattr(self._train_step_fn,
                                     "last_dispatch_flops", None) or 0.0
        self._dirty = True
        # reference-only rebind (no sync): the network must never be
        # left pointing at the donated pre-step buffers
        self._rebind_network_state()
        # sampled collective device timing (ISSUE 13): the zero step's
        # exchange is fused inside the donated program, so its cost is
        # priced by an isolated same-shape probe — first step always
        # (the dry-run/bench canaries see it), then at the
        # FLAGS_collective_timing_every stride. Host-side, outside the
        # step: the probe blocks on ITS OWN tiny program, never on the
        # in-flight train step.
        if self._zero_stage and self._zero_mesh is not None \
                and self._zero_layout is not None:
            from ..distributed import collective as _collective
            # stride keyed per comm mode: flipping fp32 -> int8 changes
            # the probed wire shape, and its FIRST step must sample too
            if _collective.timing_sampled(
                    f"zero_step_probe_{self._grad_comm}"):
                try:
                    _zero.time_step_collectives(
                        self._zero_mesh, self._zero_layout,
                        self._grad_comm)
                except Exception:                        # noqa: BLE001
                    pass    # a failed probe must never fail a train step
        return loss, outs

    def _ensure_train_built(self):
        if self._train_step_fn is None or self._params is None:
            self.network.train()
            self._sync_state_from_network()
        elif self._frozen is not None and \
                getattr(self, "_bind_params", None):
            # cheap staleness probe (attr reads over the cached binds, no
            # module walk): stop_gradient flips between raw train_batch
            # calls must re-trace + reconcile optimizer slots exactly as
            # they do at fit() start — otherwise the frozen split baked
            # into the jitted step silently keeps training frozen params
            frozen_now = {n for n, p in self._bind_params
                          if p.stop_gradient}
            if frozen_now != self._frozen:
                self._sync_state_from_network()
        if self._train_step_fn is None:  # fresh build or forced re-trace
            self._build_train_step()

    def train_batch(self, inputs, labels=None, update=True,
                    return_numpy=True):
        """One optimizer step.  ``return_numpy=False`` returns the loss as
        a device scalar WITHOUT blocking on the chip — jax's async dispatch
        then pipelines successive steps (the reference's dygraph step is
        synchronous by construction; on TPU a per-step host sync costs
        tens of ms through the runtime, so the non-blocking form is the
        fast path for tight loops)."""
        adapter = self._static()
        if adapter is not None:
            return adapter.train_batch(inputs, labels)
        loss, outs, lbs = self._timed_dispatch(inputs, labels)
        metrics = self._update_metrics(outs, lbs)
        if return_numpy:
            loss = float(loss)
        return (loss, metrics) if metrics else loss

    def _timed_dispatch(self, inputs, labels):
        """Build-if-needed + span + one async dispatch: the shared body
        of train_batch and fit's inner loop. Returns device (loss, outs)
        plus the coerced label arrays (for metric updates).

        hapi/step_time_ms is HOST wall time of the step call: jax
        dispatches asynchronously, so this measures dispatch+tracing,
        not device compute — the span/histogram pair still localises
        stalls (compiles, H2D, syncs)."""
        t0 = time.perf_counter()
        with _prof.record("hapi/train_batch", "hapi",
                          args={"step": self._step_counter + 1}):
            self._ensure_train_built()
            ins = _as_arrays(inputs)
            lbs = _as_arrays(labels) if labels is not None else []
            if self._zero_stage and self._zero_layout is not None:
                self._zero_batch_guard(ins + lbs)
            loss, outs = self._dispatch_train_step(ins, lbs)
        stat_observe("hapi/step_time_ms", (time.perf_counter() - t0) * 1e3)
        return loss, outs, lbs

    def eval_batch(self, inputs, labels=None):
        adapter = self._static()
        if adapter is not None:
            return adapter.eval_batch(inputs, labels)
        with _prof.record("hapi/eval_batch", "hapi"):
            if self._eval_step_fn is None:
                self._build_eval_step()
            if self._params is None:
                self._sync_state_from_network()
            ins = _as_arrays(inputs)
            lbs = _as_arrays(labels) if labels is not None else []
            key = jax.random.key(0)
            loss, outs = self._eval_step_fn(
                self._params, self._buffers, key, len(ins), *ins, *lbs)
            metrics = self._update_metrics(outs, lbs)
            loss = float(loss)
        return (loss, metrics) if metrics else loss

    def predict_batch(self, inputs):
        adapter = self._static()
        if adapter is not None:
            return adapter.predict_batch(inputs)
        if self._eval_step_fn is None:
            self._build_eval_step()
        if self._params is None:
            self._sync_state_from_network()
        ins = _as_arrays(inputs)
        _, outs = self._eval_step_fn(
            self._params, self._buffers, jax.random.key(0), len(ins), *ins)
        return [np.asarray(o) for o in outs]

    def _update_metrics(self, outs, labels):
        results = []
        for m in self._metrics:
            # wrap labels directly — np.asarray on a device-resident label
            # batch is a blocking D2H sync per step
            correct = m.compute(*[Tensor(o) for o in outs],
                                *[Tensor(l) for l in labels])
            r = m.update(*(correct if isinstance(correct, tuple)
                           else (correct,)))
            results.append(r)
        return results

    # -- fit/evaluate/predict ------------------------------------------------
    def _as_loader(self, data, batch_size, shuffle, num_workers, drop_last):
        if data is None or isinstance(data, DataLoader):
            return data
        return DataLoader(data, batch_size=batch_size, shuffle=shuffle,
                          num_workers=num_workers, drop_last=drop_last)

    def _zero_batch_guard(self, arrays):
        """The helpful face of the zero=1 batch contract: every array's
        axis 0 must split evenly across dp. Raised from BOTH entries —
        the dispatch path and the prefetch producer (which would
        otherwise surface jax's opaque 'global size of its dimension 0
        should be divisible' from the dp-sharded device_put on a tail
        batch)."""
        dp = self._zero_layout.dp if self._zero_layout is not None \
            else None
        if not dp:
            return
        for a in arrays:
            shape = getattr(a, "shape", ())
            if shape and shape[0] % dp:
                raise ValueError(
                    f"fit(zero=1) splits the batch across dp={dp} "
                    f"replicas but got axis-0 size {shape[0]}; use a "
                    f"batch size divisible by dp (drop_last=True for "
                    f"the tail)")

    def _maybe_prefetch(self, loader, prefetch, buffer_size=2,
                        train=False):
        """Wrap ``loader`` in io.device_prefetch unless switched off by
        the ``prefetch`` argument (None defers to FLAGS_hapi_prefetch) or
        static mode. Sharding-aware: set ``model._prefetch_sharding`` to
        a jax.sharding.Sharding to land batches pre-sharded. With the
        ZeRO-sharded step armed (fit(zero=1)) and no explicit override,
        TRAIN batches derive the step's own dp batch sharding — they
        land pre-split across the mesh instead of replicated-then-
        resharded (a gather the sharded train state never needs)."""
        from ..framework.flags import flag_value
        if loader is None or self._static() is not None:
            return loader
        if prefetch is None:
            prefetch = bool(flag_value("FLAGS_hapi_prefetch"))
        if not prefetch:
            return loader
        from ..io import device_prefetch
        sharding = getattr(self, "_prefetch_sharding", None)
        if sharding is None and train and self._zero_stage \
                and self._zero_mesh is not None:
            sharding = _zero.dp_sharding(self._zero_mesh)

            def _guarded(it):
                # validate BEFORE the dp-sharded device_put: a
                # non-divisible tail batch must fail with the
                # drop_last=True hint, not jax's sharding error from
                # the prefetch producer thread
                for batch in it:
                    arrays = batch if isinstance(batch, (list, tuple)) \
                        else [batch]
                    self._zero_batch_guard(
                        [getattr(a, "_data", a) for a in arrays])
                    yield batch

            loader = _guarded(loader)
        return device_prefetch(loader, sharding=sharding,
                               buffer_size=buffer_size)

    def _flush_window(self, window):
        """ONE host sync for a window of buffered device step results:
        fetch the last loss (its value bounds every queued step, so this
        is the only pipeline stall), then run the windowed metric updates
        — their D2H copies read already-computed arrays. Counted in
        ``hapi/host_sync`` so the sync budget of fit() is asserted by
        tests and bench.py --dry-run, not assumed."""
        if not window:
            return {}
        t0 = time.perf_counter()
        with _prof.record("hapi/host_sync", "hapi",
                          args={"steps": len(window)}):
            loss = float(np.asarray(window[-1][0]).ravel()[0])
            metrics = []
            for _, outs, lbs in window:
                if outs is not None:
                    metrics = self._update_metrics(outs, lbs)
        window.clear()
        stat_add("hapi/host_sync")
        stat_observe("hapi/host_sync_ms",
                     (time.perf_counter() - t0) * 1e3)
        logs = self._pack_logs((loss, metrics) if metrics else loss)
        logs.update(self._observe_compute())
        # HBM watermark at the step-boundary surface (the flush already
        # blocks on the host sync; one PjRt stats query rides along)
        _memory.sample("hapi/flush", steps=self._step_counter)
        # numerics: decode the window's audit vectors (already-computed
        # device arrays behind the loss fetch above — no extra sync, the
        # hapi/host_sync counter is untouched), feed the telemetry
        # histograms + the training flight recorder, and apply the
        # policy — 'halt' raises NumericsError here, AFTER its anomaly
        # postmortem dump, and propagates through fit's on_train_abort
        # teardown like any other training failure
        logs.update(self._flush_numerics())
        return logs

    def _flush_numerics(self):
        """Drain the window's audit vectors into the numerics recorder
        (profiler/numerics.py). Returns the flush-log update
        (``grad_norm`` + ``loss_scale``); raises only
        :class:`~paddle_tpu.profiler.numerics.NumericsError` (halt
        mode) — recorder bugs degrade to a warning, never kill a run
        the audit exists to protect."""
        if not self._audit_window:
            return {}
        entries = list(self._audit_window)
        self._audit_window.clear()
        rec = self._numerics_recorder
        if rec is None:
            return {}
        retrace_now = stat_get("dispatch/retrace_cause")
        delta = retrace_now - self._retrace_mark
        self._retrace_mark = retrace_now
        from ..amp import active_scaler
        # the process's newest ENABLED scaler: hapi's bf16-native step
        # drives no GradScaler itself, so the recorded state is ambient
        # context (which custom-AMP-loop scaler was live during this
        # fit), not a claim that fit consumed it
        scaler = active_scaler()
        kwargs = dict(
            mode=self._numerics_mode,
            lr=float(self._optimizer.get_lr()),
            scaler=scaler.state() if scaler is not None else None,
            retrace_delta=int(delta),
            ledger_bytes=_memory.ledger_total(),
            context={"site": getattr(getattr(self, "_probe_site", None),
                                     "name", None)})
        try:
            # decode each vector against the layout IT was produced
            # under: a mid-window step rebuild (frozen-set flip via the
            # staleness probe) changes the group schema, and zipping an
            # old vector against the new groups would silently blame
            # the wrong layers. Consecutive same-layout runs share one
            # record_window call.
            logs = {}
            i, n = 0, len(entries)
            while i < n:
                layout = entries[i][2]
                j = i
                while j < n and entries[j][2] is layout:
                    j += 1
                if layout is not None:
                    logs = rec.record_window(
                        [(step, np.asarray(a))
                         for step, a, _ in entries[i:j]],
                        layout, **kwargs)
                i = j
            return logs
        except _numerics.NumericsError:
            raise
        except Exception as e:  # pragma: no cover - recorder robustness
            import warnings
            warnings.warn(f"numerics flush failed "
                          f"({type(e).__name__}: {e}); continuing fit",
                          RuntimeWarning)
            return {}

    def _observe_compute(self):
        """Achieved FLOP/s (and MFU against the device peak) for the
        steps dispatched since the last flush, from the train step's
        program-registry cost analysis: ``hapi/flops_per_sec`` always
        when the backend reports FLOPs, ``hapi/mfu`` (plus an ``mfu``
        entry in the flush logs, which the ProgBar prints) only when a
        peak is known — the per-device table in
        ``framework/program_registry.py``, overridable with
        ``PADDLE_TPU_PEAK_FLOPS``; CPU has no honest peak. The FIRST
        window includes trace+compile wall time, exactly like
        ``hapi/step_time_ms``."""
        now = time.perf_counter()
        flops, self._flush_flops = self._flush_flops, 0.0
        steps, self._flush_steps = self._flush_steps, 0
        # re-arm lazily (next dispatch stamps the window start), NOT at
        # `now`: eval/checkpoint wall time between the epoch-end flush
        # and the next epoch's first batch must not deflate the next
        # window's FLOP/s into a fake per-epoch MFU dip
        t0, self._flush_t0 = self._flush_t0, None
        out = {}
        if not flops or not steps or t0 is None:
            return out
        wall = now - t0
        if wall <= 0:
            return out
        achieved = flops / wall
        stat_observe("hapi/flops_per_sec", achieved)
        peak = _registry.peak_flops()
        if peak:
            out["mfu"] = achieved / peak
            stat_observe("hapi/mfu", out["mfu"])
        return out

    def _update_memory_ledger(self):
        """Register the train state's bytes in the HBM ledger
        (profiler/memory.py) — the 'what WE think is live' side of the
        ledger-vs-device crosscheck. Host arithmetic over avals only.

        Keys are per-INSTANCE (the train step's probe-site name as the
        prefix) so two Models in one process never alias each other's
        entries, and a weakref finalizer drops them when the Model is
        collected — a discarded model must not haunt the crosscheck or
        an OOM postmortem with train state that is no longer live."""
        import weakref

        def tree_bytes(tree):
            return sum(int(getattr(v, "nbytes", 0))
                       for v in jax.tree_util.tree_leaves(tree or {}))
        base = getattr(self, "_ledger_base", None)
        if base is None:
            site = getattr(self, "_probe_site", None)
            name = site.name if site is not None else \
                f"hapi/train_step[{type(self.network).__name__}" \
                f"@{id(self):x}]"
            base = self._ledger_base = name.replace(
                "hapi/train_step", "hapi/state", 1)
            keys = [f"{base}/params", f"{base}/opt_state",
                    f"{base}/buffers"]
            weakref.finalize(self, _drop_ledger_keys, keys)
        _memory.ledger_set(f"{base}/params", tree_bytes(self._params))
        # the ledger records PER-REPLICA residency (what one chip
        # holds): a dp-sharded opt state (fit(zero=1)) bills its flat
        # stripes at 1/dp of the logical bytes — the HBM win the ZeRO
        # rewrite exists for, proven by the same ledger that would
        # catch it regressing
        opt_bytes = tree_bytes(self._opt_state)
        if self._opt_state is not None and \
                _zero.is_sharded_state(self._opt_state) and \
                self._zero_layout is not None:
            flat_bytes = tree_bytes(self._opt_state.get("flat"))
            opt_bytes = (opt_bytes - flat_bytes
                         + flat_bytes // self._zero_layout.dp)
        _memory.ledger_set(f"{base}/opt_state", opt_bytes)
        _memory.ledger_set(f"{base}/buffers", tree_bytes(self._buffers))

    def fit(self, train_data=None, eval_data=None, batch_size=1, epochs=1,
            eval_freq=1, log_freq=10, save_dir=None, save_freq=1, verbose=2,
            drop_last=False, shuffle=True, num_workers=0, callbacks=None,
            prefetch=None, prefetch_buffer_size=2, analyze=None,
            numerics=None, zero=None, grad_comm=None):
        """Train over ``train_data``, asynchronously on the dygraph path:
        steps are dispatched without blocking (donated jitted step), the
        next batch's H2D transfer rides under compute via
        ``io.device_prefetch`` (``prefetch=None`` defers to
        ``FLAGS_hapi_prefetch``; pass False for iterables that must not
        be read ahead), and loss/metrics stay device values flushed to
        the host only every ``log_freq`` steps and at epoch end — O(steps
        / log_freq) host syncs per epoch (the ``hapi/host_sync`` counter)
        instead of one stall per batch (with metrics attached the window
        additionally caps at ``_METRIC_WINDOW`` steps so pinned outputs
        stay bounded). Between flushes,
        ``on_train_batch_end`` receives the last flushed logs, so
        per-step scalar consumers (e.g. VisualDL) see values at
        ``log_freq`` granularity on this path; the static-graph adapter
        keeps per-step logs (its executor is host-synchronous anyway).

        ``analyze`` runs the jaxpr linter (paddle_tpu/analysis) over the
        built train step on the first batch: ``'warn'`` logs findings,
        ``'error'`` raises AnalysisError on error-severity ones,
        ``'off'`` skips. ``None`` defers to ``FLAGS_static_analysis``
        (env-seeded, default off). Tracing only — nothing executes.

        ``numerics`` arms the training numerics health layer
        (profiler/numerics.py): a device-side audit (finite bitmask,
        grad/param/update norms, per-layer-group nonfinite counts)
        FUSED into the donated train step and fetched only at the flush
        windows — zero extra host syncs (``hapi/host_sync`` is
        identical on/off) and zero extra compiled programs. ``'record'``
        feeds the ``hapi/grad_norm``/``update_ratio``/
        ``grad_clip_ratio`` histograms and the bounded training flight
        recorder; ``'warn'`` additionally dumps an anomaly postmortem
        JSON and warns on nonfinite steps or robust-z loss spikes;
        ``'halt'`` raises :class:`NumericsError` on a nonfinite step
        AFTER the postmortem lands (``on_train_abort`` teardown runs).
        ``None`` defers to ``FLAGS_numerics`` /
        ``FLAGS_check_nan_inf`` (the reference flag's abort-on-NaN
        semantics map to ``'halt'``), default ``'off'``.

        ``zero=1`` arms the ZeRO-sharded weight update (hapi/zero.py,
        arXiv 2004.13336): the donated train step runs inside a
        ``shard_map`` over the dp mesh axis — reduce-scatter grads,
        shard-local optimizer over a 1/dp stripe of the (flat,
        dp-sharded) optimizer state, all-gather updated params — one
        compiled donated program, bit-identical training math, and
        per-replica opt-state HBM cut ~dp-fold (the PR-7 ledger bills
        the stripes). Optimizer state lives SHARDED between steps;
        ``state_dict``/``save``/the eager bridge gather on demand and
        ``load`` re-shards, so checkpoints are mode-portable. ``None``
        defers to ``FLAGS_zero_stage`` (default 0). Batch axis 0 must
        divide by dp, and the loss must be an equal-weight MEAN over
        the batch (every built-in loss's default reduction): the
        gradient exchange averages per-slice gradients, the standard
        data-parallel contract (``paddle.DataParallel``/DDP) — a
        ``reduction='sum'`` loss, or one whose per-sample weights
        concentrate unevenly in a slice (``ignore_index``), follows
        the dp-averaged semantics, not the single-process ones.
        ``grad_comm='int8'`` additionally runs the
        gradient exchange quantized (EQuARX-style per-chunk max-abs
        scales computed in-step, ~4x fewer wire bytes — the
        ``collective_bytes/*`` counters prove it), with the numerics
        audit reading the DEQUANTIZED gradient so corruption is blamed
        at the exact step; default ``'fp32'`` (exact), ``None`` defers
        to ``FLAGS_grad_comm``."""
        analyze_explicit = analyze is not None
        if analyze is None:
            # flag-seeded: lenient normalization (a bad env value means
            # un-linted, not a crash blaming an argument never passed)
            from .. import analysis
            analyze = analysis.flag_mode()
        elif analyze not in ("off", "warn", "error"):
            raise ValueError(
                f"analyze must be 'warn', 'error' or 'off', got "
                f"{analyze!r}")
        numerics_explicit = numerics is not None
        if numerics is None:
            numerics = _numerics.flag_mode()
        elif numerics not in _numerics.MODES:
            raise ValueError(
                f"numerics must be one of {_numerics.MODES}, got "
                f"{numerics!r}")
        zero_explicit = zero is not None
        if zero is None:
            # env-seeded, leniently normalized like the sibling flags:
            # a bad FLAGS_zero_stage value means replicated, not a
            # crash blaming an argument that was never passed
            from ..framework.flags import flag_value
            try:
                zero = 1 if int(flag_value("FLAGS_zero_stage") or 0) \
                    >= 1 else 0
            except (TypeError, ValueError):
                zero = 0
        elif zero in (0, 1, False, True):
            zero = int(zero)
        else:
            raise ValueError(
                f"zero must be 0 or 1 (ZeRO stage-1 optimizer-state "
                f"sharding), got {zero!r}")
        if grad_comm is None:
            from ..framework.flags import flag_value
            gc = str(flag_value("FLAGS_grad_comm") or "fp32").strip() \
                .lower()
            grad_comm = gc if gc in ("fp32", "int8") else "fp32"
        elif grad_comm not in ("fp32", "int8"):
            raise ValueError(
                f"grad_comm must be 'fp32' or 'int8', got {grad_comm!r}")
        loader = self._as_loader(train_data, batch_size, shuffle,
                                 num_workers, drop_last)
        eval_loader = self._as_loader(eval_data, batch_size, False,
                                      num_workers, False)
        try:
            steps = len(loader)
        except TypeError:
            steps = None
        cbks = config_callbacks(
            callbacks, model=self, epochs=epochs, steps=steps,
            log_freq=log_freq, verbose=verbose, save_freq=save_freq,
            save_dir=save_dir, metrics=self._metric_names())
        self.stop_training = False
        self.network.train()
        async_path = self._static() is None
        if analyze != "off" and not async_path:
            # the jaxpr linter hooks the DYNAMIC donated train step; on
            # the static-graph adapter the analog is the Executor.run
            # pre-flight. Warn only for an EXPLICIT analyze= request
            # (error mode could never fire) — a flag-seeded mode already
            # covers static programs through that pre-flight, so there
            # is nothing to advise
            if analyze_explicit:
                import warnings
                warnings.warn(
                    "fit(analyze=...) applies to the dynamic-graph path; "
                    "in static mode the FLAGS_static_analysis pre-flight "
                    "at Executor.run lints the captured Program",
                    UserWarning)
            analyze = "off"
        if numerics != "off" and not async_path:
            # the audit is fused into the DYNAMIC donated train step;
            # the static-graph Executor is host-synchronous per batch —
            # its loss is already on the host every step
            if numerics_explicit:
                import warnings
                warnings.warn(
                    "fit(numerics=...) applies to the dynamic-graph "
                    "path; the static-graph executor fetches the loss "
                    "every batch already", UserWarning)
            numerics = "off"
        if zero and not async_path:
            # the sharded weight update lives in the DYNAMIC donated
            # step; the static-graph executor replays a captured
            # Program per batch
            if zero_explicit:
                import warnings
                warnings.warn(
                    "fit(zero=...) applies to the dynamic-graph path; "
                    "the static-graph executor runs the captured "
                    "Program unsharded", UserWarning)
            zero = 0
        if async_path:
            # off<->on changes the step's trace (the audit output and
            # inject scalar are part of the program); record/warn/halt
            # share it — the policy is host-side, switching is free
            if (numerics != "off") != self._audit_enabled \
                    and self._train_step_fn is not None:
                self._train_step_fn = None
            # a zero-stage or grad-comm flip is a different program:
            # invalidate the step (the opt-state layout transition —
            # shard or gather — happens in _sync_state_from_network)
            if (zero != self._zero_stage
                    or (zero and grad_comm != self._grad_comm)) \
                    and self._train_step_fn is not None:
                self._train_step_fn = None
            self._zero_stage, self._grad_comm = zero, grad_comm
            self._numerics_mode = numerics
            self._sync_state_from_network()
            if self._train_step_fn is None:
                self._build_train_step()
            self._update_memory_ledger()
            if numerics != "off":
                if self._numerics_recorder is None:
                    self._numerics_recorder = _numerics.NumericsRecorder()
                # ring continuity is kept across fits; the loss-spike
                # baseline is not (a new task's healthy starting loss
                # must not z-score against the last run's converged one)
                self._numerics_recorder.new_run()
                self._audit_window = deque(maxlen=self._AUDIT_WINDOW)
                self._audit_collect = True
                self._retrace_mark = stat_get("dispatch/retrace_cause")
            else:
                # an ABORTED numerics fit can leave un-flushed vectors
                # behind (collect stops in the finally, the window does
                # not drain) — a later numerics-off fit must not decode
                # the previous run's leftovers into the recorder
                self._audit_window.clear()
        self._flush_flops, self._flush_steps, self._flush_t0 = 0.0, 0, None
        cbks.on_train_begin()
        try:
            for epoch in range(epochs):
                if self.stop_training:
                    break
                cbks.on_epoch_begin(epoch)
                for m in self._metrics:
                    m.reset()
                logs = {}
                window = []
                data_iter = self._maybe_prefetch(loader, prefetch,
                                                 prefetch_buffer_size,
                                                 train=True)
                for step, batch in enumerate(data_iter):
                    cbks.on_train_batch_begin(step)
                    inputs, labels = self._split_batch(batch)
                    if (analyze != "off" and async_path
                            and epoch == 0 and step == 0):
                        self._analysis_report = self._run_analysis(
                            inputs, labels, analyze)
                    if not async_path:
                        result = self.train_batch(inputs, labels)
                        logs = self._pack_logs(result)
                    else:
                        loss, outs, lbs = self._timed_dispatch(inputs,
                                                               labels)
                        # without metrics the outputs are dead weight —
                        # drop the refs so XLA frees them immediately
                        # (GPT-size logits held over a window would
                        # otherwise pin log_freq batches of HBM); WITH
                        # metrics the window itself must pin outputs, so
                        # its length is capped: at most _METRIC_WINDOW
                        # batches of outputs live on device even when
                        # log_freq is large
                        entry = (loss, outs if self._metrics else None,
                                 lbs if self._metrics else None)
                        if self._metrics or not window:
                            window.append(entry)
                        else:
                            # loss-only window: _flush_window reads just
                            # the last loss, so keep O(1) device buffers
                            # alive however large log_freq is
                            window[0] = entry
                        # log_freq <= 0 means "epoch-end flushes only"
                        # (pre-async fit accepted 0 as 'never log')
                        if (log_freq > 0 and step % log_freq == 0) or (
                                self._metrics and
                                len(window) >= self._METRIC_WINDOW):
                            logs = self._flush_window(window)
                    cbks.on_train_batch_end(step, logs)
                if window:  # tail of the epoch since the last flush
                    logs = self._flush_window(window)
                cbks.on_epoch_end(epoch, logs)
                if eval_loader is not None and (epoch + 1) % eval_freq == 0:
                    self.evaluate(eval_loader, batch_size=batch_size,
                                  verbose=verbose, callbacks=cbks,
                                  prefetch=prefetch, _inside_fit=True)
            cbks.on_train_end()
        except BaseException as e:
            # an out-of-HBM death leaves the memory picture behind: the
            # tracker's timeline, the ledger (params/opt_state/buffers +
            # KV pools), and the largest live arrays, as JSON next to
            # the serving flight recorder's dumps. Best-effort — the
            # postmortem can never mask the original error.
            if _memory.is_resource_exhausted(e):
                _memory.oom_postmortem(e, extra={"phase": "Model.fit"})
            # teardown-only hook: a failed fit must not leak callback-held
            # process-global state (ProfilerCallback's armed span session),
            # but on_train_end keeps its success-only semantics (e.g.
            # ModelCheckpoint's 'final' save). CallbackList.on_train_abort
            # isolates per-callback errors so none can mask the in-flight
            # training exception.
            cbks.on_train_abort()
            raise
        finally:
            self._audit_collect = False
            self._sync_state_to_network()

    def evaluate(self, eval_data, batch_size=1, log_freq=10, verbose=2,
                 num_workers=0, callbacks=None, prefetch=None,
                 _inside_fit=False):
        loader = self._as_loader(eval_data, batch_size, False, num_workers,
                                 False)
        self.network.eval()
        if self._static() is None:
            if self._params is None:
                self._sync_state_from_network()
            self._eval_step_fn = None  # re-trace in eval mode
        for m in self._metrics:
            m.reset()
        cbks = callbacks if _inside_fit else config_callbacks(
            callbacks, model=self, verbose=verbose,
            metrics=self._metric_names())
        cbks.on_eval_begin()
        total_loss, n = 0.0, 0
        data_iter = self._maybe_prefetch(loader, prefetch)
        for step, batch in enumerate(data_iter):
            cbks.on_eval_batch_begin(step)
            inputs, labels = self._split_batch(batch)
            result = self.eval_batch(inputs, labels)
            loss = result[0] if isinstance(result, tuple) else result
            total_loss += loss
            n += 1
            cbks.on_eval_batch_end(step, self._pack_logs(result))
        logs = {"loss": total_loss / max(1, n)}
        for m in self._metrics:
            names = m.name() if isinstance(m.name(), list) else [m.name()]
            vals = m.accumulate()
            vals = vals if isinstance(vals, list) else [vals]
            logs.update(dict(zip(names, vals)))
        cbks.on_eval_end(logs)
        self.network.train()
        self._eval_step_fn = None  # next eval retraces with train=False
        return logs

    def predict(self, test_data, batch_size=1, num_workers=0,
                stack_outputs=False, callbacks=None, verbose=1):
        loader = self._as_loader(test_data, batch_size, False, num_workers,
                                 False)
        self.network.eval()
        if self._static() is None:
            if self._params is None:
                self._sync_state_from_network()
            self._eval_step_fn = None
        outputs = []
        for batch in loader:
            inputs, _ = self._split_batch(batch, predict=True)
            outs = self.predict_batch(inputs)
            outputs.append(outs)
        self.network.train()
        self._eval_step_fn = None
        if not outputs:
            return []
        n_out = len(outputs[0])
        grouped = [[b[i] for b in outputs] for i in range(n_out)]
        if stack_outputs:
            grouped = [np.concatenate(g) for g in grouped]
        return grouped

    def _split_batch(self, batch, predict=False):
        if not isinstance(batch, (list, tuple)):
            return [batch], []
        batch = list(batch)
        if predict:
            # without an explicit inputs spec, a (sample, label) dataset
            # feeds only the sample (the reference relies on the spec too)
            n_in = len(self._inputs) if self._inputs else \
                (1 if len(batch) > 1 else len(batch))
            return batch[:n_in], []
        n_in = len(self._inputs) if self._inputs else len(batch) - 1
        n_in = max(1, n_in)
        return batch[:n_in], batch[n_in:]

    def _pack_logs(self, result):
        if isinstance(result, tuple):
            loss, metrics = result
        else:
            loss, metrics = result, []
        logs = {"loss": float(np.asarray(loss).ravel()[0])}
        for m, r in zip(self._metrics, metrics):
            names = m.name() if isinstance(m.name(), list) else [m.name()]
            vals = r if isinstance(r, list) else [r]
            logs.update({k: float(np.asarray(v).ravel()[0])
                         for k, v in zip(names, vals)})
        return logs

    def _metric_names(self):
        names = ["loss"]
        for m in self._metrics:
            n = m.name()
            names.extend(n if isinstance(n, list) else [n])
        return names

    def dump_numerics(self, path=None):
        """On-demand snapshot of the training numerics flight recorder
        (ring tail, anomalies, scaler state, monitor snapshot, memory
        postmortem path) as JSON — the operator surface mirroring
        ``GenerationEngine.dump_flight_recorder``. Returns the file
        path, or ``None`` when numerics was never armed on this
        Model."""
        rec = self._numerics_recorder
        if rec is None:
            return None
        return rec.postmortem(None, path=path, context={
            "site": getattr(getattr(self, "_probe_site", None), "name",
                            None)})

    # -- persistence ---------------------------------------------------------
    def save(self, path, training=True):
        self._sync_state_to_network()
        if not training:
            # reference hapi/model.py save(training=False): export the
            # inference artifact instead of raw weights. jit.save owns
            # the eval-capture/mode-restore dance.
            from .. import jit
            jit.save(self.network, path, input_spec=self._inputs or None)
            return
        _save(self.network.state_dict(), path + ".pdparams")
        if self._optimizer is not None:
            _save(self._optimizer.state_dict(), path + ".pdopt")

    def load(self, path, skip_mismatch=False, reset_optimizer=False):
        state = _load(path + ".pdparams")
        self.network.set_state_dict(state)
        self._params = None  # force re-sync
        self._train_step_fn = None
        opt_path = path + ".pdopt"
        if not reset_optimizer and self._optimizer is not None and \
                os.path.exists(opt_path):
            self._optimizer.set_state_dict(_load(opt_path))
            self._opt_state = None
            # checkpoints written after fit() carry tree-named slots;
            # arm the adoption bridge (Optimizer._ensure_slots) so an
            # eager opt.step() straight after load migrates them instead
            # of bias-correcting fresh zeros at the carried step count
            self._optimizer._slot_aliases = {
                p.name: n for n, p in self.network.named_parameters()}

    def parameters(self, *args, **kwargs):
        self._sync_state_to_network()
        return self.network.parameters()

    def summary(self, input_size=None, dtype=None):
        n_params = sum(int(np.prod(p.shape))
                       for p in self.network.parameters())
        lines = [repr(self.network),
                 f"Total params: {n_params:,}"]
        s = "\n".join(lines)
        print(s)
        return {"total_params": n_params}
