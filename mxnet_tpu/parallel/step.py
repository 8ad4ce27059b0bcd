"""Fused, sharded training step.

This is the TPU-native performance path (SURVEY.md §7.2 M6/M7): where the
reference runs forward (CachedOp) → backward (engine) → kvstore pushpull →
per-weight optimizer kernels as thousands of engine ops, here the WHOLE
training step — forward, loss, backward, gradient reduction, optimizer —
compiles into ONE XLA program over the device mesh:

  * parameters/optimizer states enter sharded per their PartitionSpec and
    are donated (buffer reuse = the reference's in-place engine updates);
  * the batch enters sharded over the "dp"/"fsdp" (+"sp") axes; gradient
    all-reduce is NOT written anywhere — XLA inserts the collectives that
    the sharding math requires (psum over dp for replicated params,
    reduce-scatter for fsdp-sharded params), executing them on ICI;
  * comm/compute overlap (the reference's priority-scheduled kvstore
    pushes, SURVEY.md §3.2c) falls out of XLA's latency-hiding scheduler.

Gluon semantics preserved: works on any initialized (Hybrid)Block, the
loss is a gluon loss block, BatchNorm running stats update through the
trace side-channel, dropout draws from a per-step key.
"""
from __future__ import annotations

import itertools
import time
from contextlib import nullcontext as _nullcontext

import jax
import jax.numpy as jnp
from jax import lax

from .. import autograd, rng as _rng
from ..base import MXNetError
from ..gluon.block import _trace_channel
from ..ndarray.ndarray import NDArray
from ..telemetry import cost as _cost
from ..telemetry import ledger as _ledger
from .mesh import PartitionSpec, current_mesh, mesh_scope, named_sharding

__all__ = ["TrainStep", "EvalStep"]


def _spec_or_replicated(spec):
    return spec if spec is not None else PartitionSpec()


def _mesh_ctx(mesh):
    """Scope for trace-inducing calls: ops (attention impl='auto') consult
    current_mesh() during tracing to pick sharded routes."""
    return mesh_scope(mesh) if mesh is not None else _nullcontext()


_step_ids = itertools.count()


class TrainStep:
    """Compile net+loss+optimizer into one sharded step program.

    Usage:
        step = TrainStep(net, loss_fn, optimizer, mesh=mesh,
                         batch_specs=(P("dp"), P("dp")))
        loss = step(data, label)          # one fused device step
        step.sync_params()                # reflect weights into the Block
    """

    def __init__(self, net, loss_fn, optimizer, mesh=None, batch_specs=None,
                 donate=True, loss_reduce="mean", n_net_inputs=1,
                 loss_scale=None, scale_window=2000, compression=None,
                 compression_threshold=0.5):
        """loss_scale: None (bf16/f32 path), a float (static scaling), or
        'dynamic' — fp16-style dynamic loss scaling run ENTIRELY inside
        the compiled step: the loss is scaled before backward, gradients
        unscaled before the optimizer, non-finite gradients skip the
        update via jnp.where, and the scale halves on overflow / doubles
        after scale_window clean steps — zero host synchronization (the
        reference's LossScaler pays a device→host check per step).

        compression='2bit': gradient reduction over the "dp" axis runs
        through the reference's 2-bit wire (quantize → all_gather of
        packed uint32 at 1/16 the f32 bytes → dequantize+sum) INSIDE the
        compiled step, with per-device error-feedback residuals in the
        step carry (donated like optimizer state) — the in-program
        successor of src/kvstore/gradient_compression.cc
        (parallel/compression.py; SURVEY §5.8 EQuARX analog). Requires a
        mesh whose only model sharding is dp replication (pure data
        parallelism) and makes BatchNorm statistics per-device (pmean'd
        into the carried moving stats — the reference's dist-kvstore BN
        behaves the same way)."""
        self.net = net
        self.loss_fn = loss_fn
        self.optimizer = optimizer
        self.mesh = mesh if mesh is not None else current_mesh()
        self.batch_specs = batch_specs
        self.donate = donate
        self.loss_reduce = loss_reduce
        self.n_net_inputs = n_net_inputs  # batch[:n] → net, batch[n:] → loss
        self._dynamic_scale = loss_scale == "dynamic"
        self._static_scale = (float(loss_scale)
                              if loss_scale not in (None, "dynamic")
                              else None)
        self._scale_window = int(scale_window)
        if compression not in (None, "2bit"):
            raise MXNetError(f"unknown compression {compression!r}")
        self._compression = compression
        self._compression_threshold = float(compression_threshold)
        if compression is not None:
            if self.mesh is None or "dp" not in self.mesh.axis_names \
                    or self.mesh.shape["dp"] < 2:
                raise MXNetError(
                    "compression='2bit' needs a mesh with a dp axis of "
                    "size >= 2 (it compresses the dp gradient exchange)")
            if any(ax != "dp" and n > 1
                   for ax, n in self.mesh.shape.items()):
                raise MXNetError(
                    "compression='2bit' supports pure data parallelism "
                    "(params replicated); drop tp/sp/pp/fsdp axes")
            if loss_reduce != "mean":
                raise MXNetError(
                    "compression='2bit' requires loss_reduce='mean' "
                    "(the compressed collective mean-reduces over dp)")
        if not optimizer.fused_supported:
            raise MXNetError(
                f"{type(optimizer).__name__} has no functional path for the "
                "fused step; use SGD/Adam/AdamW/LAMB or the eager Trainer")
        params = net.collect_params()
        self._params = [p for p in params.values()]
        self._trainable = [p.grad_req != "null" for p in self._params]
        # per-parameter lr/wd multipliers are static. Parity with the eager
        # Trainer: it sets optimizer.param_dict, so _get_lr/_get_wd use the
        # Parameter's own lr_mult/wd_mult and never consult the name-keyed
        # set_lr_mult/set_wd_mult dicts — mirror exactly that.
        self._lr_mults = [p.lr_mult for p in self._params]
        self._wd_mults = [p.wd_mult for p in self._params]
        for p in self._params:
            if p._data is None:
                raise MXNetError(
                    f"parameter {p.name} not initialized; run one forward "
                    "or set shapes before building TrainStep")
        # own copies: step buffers are DONATED to XLA each call, and the
        # source NDArrays may be aliased elsewhere (donating a shared
        # buffer would delete it under the other holder's feet)
        self._param_arrays = [jnp.copy(p.data()._data)
                              for p in self._params]
        self._opt_states = tuple(
            optimizer.init_state_arrays_mp(a) if tr else ()
            for a, tr in zip(self._param_arrays, self._trainable))
        self._t = jnp.zeros((), jnp.int32)
        # per-device error-feedback residuals (leading dp axis, sharded)
        self._residuals = ()
        if self._compression is not None:
            n_dp = self.mesh.shape["dp"]
            with mesh_scope(self.mesh):
                rspec = named_sharding(PartitionSpec("dp"))
                self._residuals = tuple(
                    jax.device_put(
                        jnp.zeros((n_dp,) + a.shape, jnp.float32), rspec)
                    for a, tr in zip(self._param_arrays, self._trainable)
                    if tr)
        # dynamic loss-scaler state lives ON DEVICE in the step carry
        self._scale_state = (jnp.asarray(2.0 ** 16, jnp.float32),
                             jnp.zeros((), jnp.int32)) \
            if self._dynamic_scale else None
        self._host_t = 0
        self._base_key = None
        self._lr_cache = None
        self._wd_cache = None
        # program cache keyed on the batch signature (shapes, dtypes,
        # arity) — the BucketingModule story (SURVEY.md §3.3): each padded
        # bucket size gets its own compiled program, parameters shared
        self._programs = {}
        self._last_sig = None
        self._last_single_sig = None
        self._meta = {}
        # device-cost + HBM-ledger integration (docs/OBSERVABILITY.md):
        # per-dispatch wall attribution is always on (cheap);
        # register_cost_analysis() adds the XLA FLOP/byte figures (it
        # re-traces, so it is an explicit call, not a hot-path default)
        self._cost_key = f"train_step{next(_step_ids)}"
        _ledger.register(self._cost_key, self._hbm_ledger)
        if self.mesh is not None:
            self._place_sharded()

    def _hbm_ledger(self):
        """telemetry.ledger provider: the step's donated device state —
        its own parameter copies, optimizer state, compression
        residuals (ledger dedupes anything shared elsewhere)."""
        return {
            "params": list(self._param_arrays),
            "optimizer_state": list(
                jax.tree_util.tree_leaves(self._opt_states)),
            "residuals": list(self._residuals),
        }

    def register_cost_analysis(self, sig=None):
        """Register the compiled step's XLA cost analysis with
        telemetry.cost (keyed `<cost_key>/step` or `/run_steps`), so
        the dispatch walls already being attributed turn into live MFU
        and roofline gauges. Re-traces the program once — call it from
        a bench/startup path, not per step. Returns the cost record or
        None when the backend reports no costs."""
        if sig is None:
            sig = self._last_single_sig or self._last_sig
        ca = self.compiled_cost_analysis(sig=sig)
        if not ca:
            return None
        d = dict(ca)
        multi = isinstance(sig, tuple) and sig and sig[0] == "multi"
        program = self._cost_key + ("/run_steps" if multi else "/step")
        flops, nbytes = d.get("flops"), d.get("bytes accessed")
        if multi:
            # compiled_cost_analysis normalizes a K-chained program to
            # per-step figures; the program record costs ONE DISPATCH,
            # so scale back up to the K-step total
            k = sig[2] if sig[2] is not None else sig[3][0][0]
            flops = flops * k if flops else flops
            nbytes = nbytes * k if nbytes else nbytes
        return _cost.register_program(program, flops, nbytes)

    # -- sharding placement ------------------------------------------------
    def _place_sharded(self):
        with mesh_scope(self.mesh):
            placed = []
            for p, a in zip(self._params, self._param_arrays):
                s = named_sharding(_spec_or_replicated(p.sharding))
                placed.append(jax.device_put(a, s))
            self._param_arrays = placed
            self._opt_states = tuple(
                tuple(jax.device_put(
                    s, named_sharding(_spec_or_replicated(p.sharding)))
                    for s in states)
                for p, states in zip(self._params, self._opt_states))

    def param_sharding_specs(self):
        return [_spec_or_replicated(p.sharding) for p in self._params]

    # -- build -------------------------------------------------------------
    def _make_core(self, n_batch):
        """The one-training-step function shared by the per-call program
        and the device-chained multi-step program:
        core(tr, opt, t, scale_state, nt, resid, key, lr, wd, batch) ->
        (new_tr, new_opt, t, new_scale, new_resid, loss, aux)."""
        net, loss_fn, opt = self.net, self.loss_fn, self.optimizer
        params = self._params
        trainable = self._trainable
        reduce = self.loss_reduce
        meta = self._meta

        def forward_loss(param_datas, batch_datas, key):
            saved = [p._data for p in params]
            _trace_channel.push_frame()
            try:
                for p, d in zip(params, param_datas):
                    arr = NDArray(d)
                    arr._grad_req = "null"
                    p._data = arr
                args = [NDArray(d) for d in batch_datas]
                n_net_in = self.n_net_inputs
                with autograd._Scope(False, True), _rng.key_scope(key):
                    out = net.forward(*args[:n_net_in])
                    outs = out if isinstance(out, tuple) else (out,)
                    loss = loss_fn(*outs, *args[n_net_in:])
            finally:
                updates = _trace_channel.pop_frame()
                for p, d in zip(params, saved):
                    p._data = d
            meta["state_updates"] = updates
            ldata = loss._data if isinstance(loss, NDArray) else loss
            if reduce == "mean":
                ldata = jnp.mean(ldata)
            elif reduce == "sum":
                ldata = jnp.sum(ldata)
            aux = tuple(u for _, u in updates)
            return ldata.astype(jnp.float32), aux

        dynamic = self._dynamic_scale
        static_scale = self._static_scale
        scale_window = self._scale_window

        # trainable params are DONATED (buffer reuse on the hot path);
        # non-trainable params (BN running stats, frozen weights) ride in
        # a separate NON-donated argument, so the returned stat updates
        # are contract-fresh buffers the Parameters can own directly — no
        # per-stat copy dispatches (106/step on ResNet-50) and no
        # reliance on XLA preserving in-program copies of equal values
        # as distinct output buffers
        nt_pos = {}  # full-list index -> position in the nt tuple
        tr_pos = {}  # full-list index -> position in the tr tuple
        for i, tr in enumerate(trainable):
            if tr:
                tr_pos[i] = len(tr_pos)
            else:
                nt_pos[i] = len(nt_pos)
        tr_lr_mults = [m for m, tr in zip(self._lr_mults, trainable) if tr]
        tr_wd_mults = [m for m, tr in zip(self._wd_mults, trainable) if tr]

        self._nt_pos, self._tr_pos = nt_pos, tr_pos

        compression = self._compression
        comp_thr = self._compression_threshold

        def core(tr_datas, opt_states, t, scale_state, nt_datas, resid,
                 base_key, lr, wd, batch_datas):
            t = t + 1
            # per-step randomness derived INSIDE the program (no host RNG
            # round-trip per step; the reference's engine-managed Philox
            # streams achieve the same "no host in the loop" property)
            key = jax.random.fold_in(base_key, t)
            if compression is not None:
                # per-device dropout streams under the dp shard_map
                key = jax.random.fold_in(key, lax.axis_index("dp"))
            if dynamic:
                scale, good = scale_state
            elif static_scale is not None:
                scale, good = jnp.asarray(static_scale, jnp.float32), None
            else:
                scale, good = None, None

            def assemble(tr_tuple):
                full, it_tr, it_nt = [], iter(tr_tuple), iter(nt_datas)
                for tr in trainable:
                    full.append(next(it_tr) if tr else next(it_nt))
                return tuple(full)

            def loss_of(trainable_params):
                ldata, aux = forward_loss(assemble(trainable_params),
                                          batch_datas, key)
                if scale is not None:  # fp16 path: backward on scaled loss
                    return ldata * scale, (ldata, aux)
                return ldata, (ldata, aux)

            (_, (loss, aux)), grads = jax.value_and_grad(
                loss_of, has_aux=True)(tr_datas)
            if scale is not None:
                inv = 1.0 / scale
                grads = tuple(
                    (g.astype(jnp.float32) * inv).astype(g.dtype)
                    for g in grads)
            ok = None
            if dynamic:
                # overflow detection runs on the RAW local grads: after
                # 2-bit quantization NaN/Inf would vanish (they compare
                # False against both thresholds → code 0) and the
                # overflow would both apply and poison the residual
                ok = jnp.asarray(True)
                for g in grads:
                    ok = ok & jnp.isfinite(g.astype(jnp.float32)).all()
                if compression is not None:
                    ok = lax.pmin(ok.astype(jnp.int32), "dp") > 0
            if compression is not None:
                # the dp gradient exchange through the 2-bit wire; the
                # reduced grads come back identical on every device
                from .compression import compressed_psum_mean
                red, new_resid = [], []
                for g, r in zip(grads, resid):
                    rg, nr = compressed_psum_mean(g, r[0], "dp",
                                                  comp_thr)
                    if ok is not None:  # overflow: residual keeps its
                        nr = jnp.where(ok, nr, r[0])  # pre-step value
                    red.append(rg.astype(g.dtype))
                    new_resid.append(nr[None])
                grads = tuple(red)
                new_resid = tuple(new_resid)
                loss = lax.pmean(loss, "dp")
                aux = tuple(lax.pmean(a.astype(jnp.float32), "dp")
                            .astype(a.dtype) for a in aux)
            else:
                new_resid = resid
            if dynamic:
                # `ok` was computed from the RAW grads above
                # an overflow step must not poison mutable layer state
                # either (BN running stats from the same corrupted
                # forward): keep each stat's incoming value
                if aux:
                    olds = []
                    for sp_param, _ in meta["state_updates"]:
                        idx = next(i for i, pp in enumerate(params)
                                   if pp is sp_param)
                        # state updates usually target non-trainable
                        # params (BN stats), but push_state_update is an
                        # open extension point — a trainable target lives
                        # in the tr tuple instead
                        olds.append(nt_datas[nt_pos[idx]]
                                    if idx in nt_pos
                                    else tr_datas[tr_pos[idx]])
                    aux = tuple(jnp.where(ok, a, o.astype(a.dtype))
                                for a, o in zip(aux, olds))

            new_params, new_states = [], []
            git = iter(grads)
            for d, st, mlr, mwd in zip(tr_datas, opt_states, tr_lr_mults,
                                       tr_wd_mults):
                g = next(git)
                plr = lr * mlr if mlr != 1.0 else lr
                pwd = wd * mwd if mwd != 1.0 else wd
                nw, ns = opt.apply_arrays_mp(d, g, st, plr, pwd, t)
                if dynamic:
                    # overflow: keep the old weights/states (skip update)
                    nw = jnp.where(ok, nw, d)
                    ns = tuple(jnp.where(ok, n, o)
                               for n, o in zip(ns, st))
                new_params.append(nw)
                new_states.append(ns)
            if dynamic:
                # in-program dynamic adjustment (reference LossScaler
                # semantics, zero host syncs)
                good = jnp.where(ok, good + 1, 0)
                grow = good >= scale_window
                # growth capped at 2^24 so a perpetually-clean run can
                # never double the scale into f32 inf (which would wedge
                # training with every update skipped)
                scale = jnp.where(
                    ok, jnp.where(grow, jnp.minimum(scale * 2.0, 2.0 ** 24),
                                  scale),
                    jnp.maximum(scale * 0.5, 1.0))
                good = jnp.where(grow, 0, good)
                new_scale_state = (scale, good)
            else:
                new_scale_state = scale_state
            return (tuple(new_params), tuple(new_states), t,
                    new_scale_state, new_resid, loss, aux)

        if compression is None:
            return core
        # compressed path: the whole step runs SPMD inside a shard_map
        # over "dp" — params/states replicated (P() prefix specs), batch
        # and residuals sharded — so the dp gradient exchange is OUR
        # 2-bit collective, not XLA's f32 psum
        from .mesh import shard_map_compat
        repl = PartitionSpec()
        dp = PartitionSpec("dp")
        bspecs = tuple(self.batch_specs or [dp] * n_batch)

        def global_core(tr_datas, opt_states, t, scale_state, nt_datas,
                        resid, base_key, lr, wd, batch_datas):
            wrapped = shard_map_compat(
                core, mesh=self.mesh,
                in_specs=(repl, repl, repl, repl, repl, dp, repl, repl,
                          repl, bspecs),
                out_specs=(repl, repl, repl, repl, dp, repl, repl),
                check_rep=False)
            return wrapped(tr_datas, opt_states, t, scale_state,
                           nt_datas, resid, base_key, lr, wd,
                           batch_datas)

        return global_core

    def _jit_shardings(self, n_batch, stacked=False):
        """(in_shardings tuple, or None when no mesh) for the step args
        (tr, opt_states, t, scale_state, nt, key, lr, wd, *batch).
        stacked=True prepends an unsharded leading steps axis to each
        batch spec (the run_steps layout)."""
        if self.mesh is None:
            return None
        trainable = self._trainable
        with mesh_scope(self.mesh):
            pspecs = [named_sharding(s)
                      for s in self.param_sharding_specs()]
            tr_pspecs = tuple(s for s, tr in zip(pspecs, trainable) if tr)
            nt_pspecs = tuple(s for s, tr in zip(pspecs, trainable)
                              if not tr)
            sspecs = tuple(
                tuple(pspecs[i] for _ in st)
                for i, st in enumerate(self._opt_states)
                if trainable[i])
            repl = named_sharding(PartitionSpec())
            raw_bspecs = (self.batch_specs or
                          [PartitionSpec("dp")] * n_batch)
            if stacked:
                raw_bspecs = [PartitionSpec(None, *tuple(s))
                              for s in raw_bspecs]
            bspecs = tuple(named_sharding(s) for s in raw_bspecs)
            sscale = jax.tree_util.tree_map(
                lambda _: repl, self._scale_state) \
                if self._scale_state is not None else ()
            rspecs = tuple(named_sharding(PartitionSpec("dp"))
                           for _ in self._residuals)
            return (tr_pspecs, sspecs, repl, sscale,
                    nt_pspecs, rspecs, repl, repl, repl) + bspecs

    def _build(self, n_batch):
        core = self._make_core(n_batch)

        def step_fn(tr_datas, opt_states, t, scale_state, nt_datas,
                    resid, base_key, lr, wd, *batch_datas):
            return core(tr_datas, opt_states, t, scale_state, nt_datas,
                        resid, base_key, lr, wd, batch_datas)

        donate = (0, 1, 2, 5) if self.donate else ()
        shardings = self._jit_shardings(n_batch)
        if shardings is not None:
            # the carried state must come back in the layout it went in
            # with: left to itself the partitioner may return, say, a
            # replicated bias sharded like the gradient that updated it,
            # and the NEXT call would be refused for the mismatch
            tr, st, repl, scale, _nt, resid = shardings[:6]
            with mesh_scope(self.mesh):
                jitted = jax.jit(
                    step_fn, in_shardings=shardings,
                    out_shardings=(tr, st, repl, scale, resid, repl, None),
                    donate_argnums=donate)
        else:
            jitted = jax.jit(step_fn, donate_argnums=donate)
        return jitted

    def _build_multi(self, n_batch, repeat_steps=None):
        """Device-chained multi-step program: lax.scan over K stacked
        batches (or the SAME batch repeat_steps times when repeat_steps
        is set), ONE dispatch for K optimizer steps. The TPU-native
        analog of the reference's engine bulk mode (MXNET_ENGINE_BULK /
        engine.bulk batching many engine ops per scheduling round,
        SURVEY.md §2.1): host dispatch cost is paid once per K steps
        instead of per step, which matters when the per-step pytree is
        large.

        Mutable layer state (BN stats) is threaded through the scan
        carry, so K chained steps accumulate stats exactly like K
        single-step calls. lr/wd are captured once per dispatch —
        host-side schedulers take effect between run_steps() calls."""
        core = self._make_core(n_batch)
        trainable = self._trainable
        params = self._params
        meta = self._meta
        nt_pos, tr_pos = self._nt_pos, self._tr_pos
        n_rep = repeat_steps

        def multi_fn(tr_datas, opt_states, t, scale_state, nt_datas,
                     resid, base_key, lr, wd, *stacked):
            def body(carry, xs):
                tr_c, opt_c, t_c, scale_c, nt_c, rs_c = carry
                (tr_n, opt_n, t_n, scale_n, rs_n, loss, aux) = core(
                    tr_c, opt_c, t_c, scale_c, nt_c, rs_c, base_key, lr,
                    wd, stacked if n_rep else xs)
                if aux:
                    # thread state updates (BN stats) into the carry the
                    # same way __call__ threads them into _param_arrays:
                    # the update wins over the optimizer write
                    nt_n = list(nt_c)
                    tr_n = list(tr_n)
                    for (p, _), new in zip(meta["state_updates"], aux):
                        idx = next(i for i, pp in enumerate(params)
                                   if pp is p)
                        if idx in nt_pos:
                            nt_n[nt_pos[idx]] = new.astype(
                                nt_c[nt_pos[idx]].dtype)
                        else:
                            tr_n[tr_pos[idx]] = new.astype(
                                tr_c[tr_pos[idx]].dtype)
                    nt_n, tr_n = tuple(nt_n), tuple(tr_n)
                else:
                    nt_n = nt_c
                return (tr_n, opt_n, t_n, scale_n, nt_n, rs_n), loss

            init = (tr_datas, opt_states, t, scale_state, nt_datas,
                    resid)
            (tr_f, opt_f, t_f, scale_f, nt_f, rs_f), losses = \
                jax.lax.scan(body, init, None if n_rep else stacked,
                             length=n_rep if n_rep else None)
            return tr_f, opt_f, t_f, scale_f, nt_f, rs_f, losses

        # nt is NOT donated even here: its input buffers may be the very
        # arrays the Parameters hold (after a prior stat write-back), and
        # they are tiny
        donate = (0, 1, 2, 5) if self.donate else ()
        shardings = self._jit_shardings(n_batch,
                                        stacked=repeat_steps is None)
        if shardings is not None:
            tr, st, repl, scale, nt, resid = shardings[:6]
            with mesh_scope(self.mesh):
                return jax.jit(
                    multi_fn, in_shardings=shardings,
                    out_shardings=(tr, st, repl, scale, nt, resid, repl),
                    donate_argnums=donate)
        return jax.jit(multi_fn, donate_argnums=donate)

    # -- run ---------------------------------------------------------------
    def __call__(self, *batch):
        datas = tuple(b._data if isinstance(b, NDArray) else jnp.asarray(b)
                      for b in batch)
        sig = tuple((tuple(d.shape), str(d.dtype)) for d in datas)
        entry = self._programs.get(sig)
        if entry is None:
            entry = {"jitted": self._build(len(datas)), "lower_args": None}
            self._programs[sig] = entry
        self._last_sig = sig
        self._last_single_sig = sig
        if self.mesh is not None:
            with mesh_scope(self.mesh):
                bspecs = (self.batch_specs or
                          [PartitionSpec("dp")] * len(datas))
                datas = tuple(
                    jax.device_put(d, named_sharding(s))
                    for d, s in zip(datas, bspecs))
        (tr_arrays, tr_states, scale_state, nt_arrays, key, lr,
         wd) = self._prepare_dispatch(entry, datas)
        t0 = time.perf_counter()
        with _mesh_ctx(self.mesh):
            out = entry["jitted"](tr_arrays, tr_states, self._t,
                                  scale_state, nt_arrays,
                                  self._residuals, key, lr, wd, *datas)
        # host dispatch wall (async — device time only when the caller
        # syncs on the loss); turns into MFU once
        # register_cost_analysis() has run
        _cost.note_dispatch(self._cost_key + "/step",
                            time.perf_counter() - t0)
        (new_tr_arrays, new_tr_states, self._t, new_scale,
         self._residuals, loss, aux) = out
        self._write_back(new_tr_arrays, new_tr_states)
        if self._scale_state is not None:
            self._scale_state = new_scale
        self._host_t += 1  # mirror of t — no device fetch in the hot loop
        self.optimizer.num_update = self._host_t
        # mutable layer state (BN stats) written back into BOTH the
        # Parameter (eager/eval visibility) AND the step's own param
        # arrays — the next step's forward reads param_datas, so without
        # the second write the stats would re-accumulate against their
        # initial values forever. Stats ride in the NON-donated nt arg,
        # so each aux output is a fresh buffer the Parameter can own
        # outright — no copies, no use-after-donate hazard.
        updates = self._meta.get("state_updates", ())
        if updates:
            idx_of = {id(p): i for i, p in enumerate(self._params)}
            for (p, _), new in zip(updates, aux):
                i = idx_of.get(id(p))
                if i is not None:
                    self._param_arrays[i] = new
                # a TRAINABLE state-update target (unusual, but
                # push_state_update is open) re-enters the donated tr
                # tuple next step — the Parameter needs its own buffer
                p._data._rebind(jnp.copy(new)
                                if (self.donate and i is not None
                                    and self._trainable[i]) else new)
        return NDArray(loss)

    def _prepare_dispatch(self, entry, datas):
        """Common per-dispatch state: (tr_arrays, tr_states, scale_state,
        nt_arrays, key, lr, wd). Also fills entry["lower_args"] on first
        use (shape structs for AOT lowering — the real arrays may be
        donated by the call)."""
        if self._base_key is None:
            self._base_key = _rng.next_key()
        # cache device scalars for lr/wd — refresh only when the host
        # value changes (schedulers); avoids 2 H2D transfers per step
        lr_v = float(self.optimizer.learning_rate)
        wd_v = float(self.optimizer.wd)
        if self._lr_cache is None or self._lr_cache[0] != lr_v:
            self._lr_cache = (lr_v, jnp.asarray(lr_v, jnp.float32))
        if self._wd_cache is None or self._wd_cache[0] != wd_v:
            self._wd_cache = (wd_v, jnp.asarray(wd_v, jnp.float32))
        key, lr, wd = self._base_key, self._lr_cache[1], self._wd_cache[1]
        scale_state = self._scale_state if self._scale_state is not None \
            else ()
        tr_arrays = tuple(a for a, tr in zip(self._param_arrays,
                                             self._trainable) if tr)
        nt_arrays = tuple(a for a, tr in zip(self._param_arrays,
                                             self._trainable) if not tr)
        tr_states = tuple(st for st, tr in zip(self._opt_states,
                                               self._trainable) if tr)
        if entry["lower_args"] is None:
            entry["lower_args"] = jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                (tr_arrays, tr_states, self._t, scale_state, nt_arrays,
                 self._residuals, key, lr, wd) + datas)
        return tr_arrays, tr_states, scale_state, nt_arrays, key, lr, wd

    def _write_back(self, new_tr, new_states):
        """Fold trainable step outputs into _param_arrays/_opt_states."""
        it_p, it_s = iter(new_tr), iter(new_states)
        for i, tr in enumerate(self._trainable):
            if tr:
                self._param_arrays[i] = next(it_p)
        self._opt_states = tuple(
            next(it_s) if tr else st
            for st, tr in zip(self._opt_states, self._trainable))

    def run_steps(self, *stacked_batch, steps=None):
        """Run K chained optimizer steps in ONE device dispatch.

        Default: each argument is the per-call batch with an extra
        leading steps axis — shapes [K, ...] where a plain __call__
        takes [...]. With steps=K given, the arguments are ordinary
        single-step batches and the SAME batch is reused K times
        (steady-state benchmarking / overfit smokes — no stacked upload).
        Returns the per-step losses as an NDArray of shape (K,).
        Equivalent to K sequential __call__s (BN stats and the RNG
        stream thread through identically), except lr/wd are sampled
        once per dispatch — host-side LR schedulers take effect between
        run_steps calls.

        TPU-native analog of the reference's engine bulk execution
        (MXNET_ENGINE_BULK, SURVEY.md §2.1): amortizes host dispatch over
        K steps."""
        datas = tuple(b._data if isinstance(b, NDArray) else jnp.asarray(b)
                      for b in stacked_batch)
        if steps is None:
            if not datas or any(d.ndim < 1 for d in datas):
                raise MXNetError("run_steps needs batches with a leading "
                                 "steps axis (or pass steps=K)")
            k = datas[0].shape[0]
            for d in datas:
                if d.shape[0] != k:
                    raise MXNetError(
                        f"run_steps: inconsistent steps axis "
                        f"{d.shape[0]} vs {k}")
        else:
            k = int(steps)
            if k <= 0:
                raise MXNetError("run_steps: steps must be positive")
        sig = ("multi", steps is None, k if steps is not None else None) \
            + tuple((tuple(d.shape), str(d.dtype)) for d in datas)
        entry = self._programs.get(sig)
        if entry is None:
            entry = {"jitted": self._build_multi(
                len(datas), repeat_steps=None if steps is None else k),
                "lower_args": None}
            self._programs[sig] = entry
        self._last_sig = sig
        if self.mesh is not None:
            with mesh_scope(self.mesh):
                raw = (self.batch_specs or
                       [PartitionSpec("dp")] * len(datas))
                if steps is None:  # stacked layout: leading K unsharded
                    raw = [PartitionSpec(None, *tuple(s)) for s in raw]
                datas = tuple(
                    jax.device_put(d, named_sharding(s))
                    for d, s in zip(datas, raw))
        (tr_arrays, tr_states, scale_state, nt_arrays, key, lr,
         wd) = self._prepare_dispatch(entry, datas)
        t0 = time.perf_counter()
        with _mesh_ctx(self.mesh):
            out = entry["jitted"](tr_arrays, tr_states, self._t,
                                  scale_state, nt_arrays,
                                  self._residuals, key, lr, wd, *datas)
        _cost.note_dispatch(self._cost_key + "/run_steps",
                            time.perf_counter() - t0)
        (new_tr, new_states, self._t, new_scale, new_nt,
         self._residuals, losses) = out
        self._write_back(new_tr, new_states)
        it_n = iter(new_nt)
        for i, tr in enumerate(self._trainable):
            if not tr:
                self._param_arrays[i] = next(it_n)
        if self._scale_state is not None:
            self._scale_state = new_scale
        self._host_t += k
        self.optimizer.num_update = self._host_t
        # stat write-back: the final nt values are fresh (non-donated-
        # input) output buffers — Parameters can own them directly
        updates = self._meta.get("state_updates", ())
        if updates:
            idx_of = {id(p): i for i, p in enumerate(self._params)}
            for p, _ in updates:
                i = idx_of.get(id(p))
                if i is not None:
                    p._data._rebind(jnp.copy(self._param_arrays[i])
                                    if (self.donate and self._trainable[i])
                                    else self._param_arrays[i])
        return NDArray(losses)

    def sync_params(self):
        """Write the step's device arrays back into the Block's Parameters
        (so save_parameters / eager eval see current weights)."""
        for p, a in zip(self._params, self._param_arrays):
            p._data._rebind(a)

    @property
    def step_count(self):
        return self._host_t

    @property
    def loss_scale(self):
        """Current dynamic loss scale (host fetch), or the static scale,
        or None on the unscaled path."""
        if self._scale_state is not None:
            return float(self._scale_state[0])
        return self._static_scale

    def compiled_cost_analysis(self, sig=None):
        """XLA's cost analysis for a compiled step program (a dict with
        'flops' etc.), or None before the first call / when the backend
        does not report costs. This is the authoritative PER-STEP flop
        count for MFU math — no hand-derived estimates. sig selects a
        program from the bucket cache; default = the last SINGLE-step
        program called. A K-chained run_steps program reports PER-STEP
        figures too: XLA's HloCostAnalysis counts a while/scan body
        once regardless of trip count, so the lax.scan-chained program
        already costs like one step (verified against the single-step
        program; no division needed)."""
        if sig is None and self._last_single_sig is not None:
            sig = self._last_single_sig
        if sig is None:
            sig = self._last_sig
        try:
            compiled = self._lowered(sig).compile()
            ca = compiled.cost_analysis()
            if isinstance(ca, (list, tuple)):
                ca = ca[0] if ca else None
            return ca
        except Exception:
            return None

    def _lowered(self, sig=None):
        """AOT-lower one cached step program (re-traces; mesh scope active
        so the trace takes the same op routes as the live step)."""
        entry = self._programs[sig if sig is not None else self._last_sig]
        with _mesh_ctx(self.mesh):
            return entry["jitted"].lower(*entry["lower_args"])


class EvalStep:
    """Jitted inference step over the mesh (forward only)."""

    def __init__(self, net, mesh=None, batch_specs=None):
        self.net = net
        self.mesh = mesh if mesh is not None else current_mesh()
        self.batch_specs = batch_specs
        self._params = list(net.collect_params().values())
        self._programs = {}

    def _build(self, n_batch):
        net, params = self.net, self._params

        def fwd(param_datas, key, *batch_datas):
            saved = [p._data for p in params]
            _trace_channel.push_frame()
            try:
                for p, d in zip(params, param_datas):
                    arr = NDArray(d)
                    arr._grad_req = "null"
                    p._data = arr
                args = [NDArray(d) for d in batch_datas]
                with autograd._Scope(False, False), _rng.key_scope(key):
                    out = net.forward(*args)
            finally:
                _trace_channel.pop_frame()
                for p, d in zip(params, saved):
                    p._data = d
            outs = out if isinstance(out, tuple) else (out,)
            return tuple(o._data for o in outs)

        if self.mesh is not None:
            with mesh_scope(self.mesh):
                repl = named_sharding(PartitionSpec())
                pspecs = tuple(
                    named_sharding(_spec_or_replicated(p.sharding))
                    for p in params)
                bspecs = tuple(named_sharding(s) for s in (
                    self.batch_specs or [PartitionSpec("dp")] * n_batch))
                return jax.jit(fwd, in_shardings=(pspecs, repl) + bspecs)
        return jax.jit(fwd)

    def __call__(self, *batch):
        datas = tuple(b._data if isinstance(b, NDArray) else jnp.asarray(b)
                      for b in batch)
        sig = tuple((tuple(d.shape), str(d.dtype)) for d in datas)
        jitted = self._programs.get(sig)
        if jitted is None:
            jitted = self._build(len(datas))
            self._programs[sig] = jitted
        key = _rng.next_key()
        param_datas = tuple(p.data()._data for p in self._params)
        with _mesh_ctx(self.mesh):
            outs = jitted(param_datas, key, *datas)
        res = tuple(NDArray(o) for o in outs)
        return res[0] if len(res) == 1 else res
