"""Zero-retrace compiled solving: the AOT front end and multi-device sharding.

Every ``AutoDiffAdjoint.solve`` call traces the full ``lax.while_loop``
program from scratch unless the caller wraps it in ``jax.jit`` themselves --
and even then, Python-side dispatch re-validates the closure every call.  In
the small-model serving regime the paper's per-step numbers target (Sec. 4),
that dispatch overhead dominates the actual integration.  This module fixes
it with the static/dynamic split the component stack now guarantees:

``CompiledSolver``
    Wraps a driver.  ``solve(...)`` looks up an LRU cache keyed on the
    driver's *static config* (hashable treedef aux) plus the shapes/dtypes of
    every dynamic argument; on a miss it AOT-compiles the solve program once
    (``jax.jit(...).lower(...).compile()`` with ``donate_argnums`` on ``y0``)
    and thereafter dispatches straight to the cached executable -- repeated
    same-shaped solves perform **zero retraces** and zero Python tracing work.
    ``compile(...)`` exposes the same machinery ahead of time: pass
    ``jax.ShapeDtypeStruct`` specs and get a callable handle back before the
    first request arrives.

``sharded_solve``
    The paper's batch parallelism extended across chips: instances are
    independent, so the batch axis shards embarrassingly across a device mesh
    via ``shard_map`` -- each device runs the full per-instance adaptive loop
    on its shard, with its own termination reduction (no cross-device sync
    inside the loop, the multi-device analogue of torchode's no-host-sync
    rule).  Results match the single-device compiled program exactly.

What is static vs dynamic (the retrace contract):

* static -- retrace on change: the vector field (by ``is`` identity: reuse
  the function object), stepper/tableau, controller coefficients, event
  specs, ``dense``/``dense_window``/``max_steps``, and every *shape/dtype*.
* dynamic -- free to vary per call: ``y0`` values, ``t_eval``/``t_start``/
  ``t_end`` values, ``dt0``, ``args`` leaves, and the tolerances
  ``rtol``/``atol`` (including per-instance vectors).

Donation caveat: XLA can only reuse a donated buffer when some *output* has
the same shape/dtype, which for a solve means the final-state regime
(``t_eval=None``: ``ys`` is ``(b, f)`` like ``y0``).  The default
``donate="auto"`` therefore donates ``y0`` exactly when ``t_eval is None``
and keeps it alive otherwise (avoiding XLA's "donated buffers were not
usable" warning on dense-output solves, where donation buys nothing).  When
donation is active the executable *consumes* the ``y0`` buffers -- reusing
the same array for a later call raises "buffer has been deleted or donated".
Serving loops that construct a fresh ``y0`` per request (the intended
pattern) never notice; set ``donate=False`` to keep caller buffers alive
unconditionally.
"""

from __future__ import annotations

import dataclasses
from collections import OrderedDict
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from ..kernels import ops
from .drivers import AutoDiffAdjoint, BacksolveAdjoint, _Driver
from .solution import Grads, Solution
from .static import freeze, frozen_setattr
from .static import leaf_key as _leaf_key
from .static import tree_key as _tree_key
from .stepper import AbstractStepper
from .terms import ODETerm


def _spec(x) -> jax.ShapeDtypeStruct:
    """Normalize a concrete array (or an existing spec) to a ShapeDtypeStruct."""
    if isinstance(x, jax.ShapeDtypeStruct):
        return x
    x = jnp.asarray(x) if not hasattr(x, "shape") else x
    return jax.ShapeDtypeStruct(x.shape, x.dtype)


class CacheInfo(NamedTuple):
    hits: int
    misses: int
    currsize: int
    maxsize: int


def _f_key(f):
    """Cache identity of the dynamics: ODETerms by value, bare callables by
    object identity (cache entries close over ``f``, keeping it alive, so an
    id can never be recycled while its entry exists)."""
    return f if isinstance(f, ODETerm) else (type(f), id(f))


def _final_state_solution(ys, t_end) -> Solution:
    """Synthesize the final-state ``Solution`` for a driver that returns only
    ``y(t_end)`` (``BacksolveAdjoint``): per-instance status/stats do not
    cross its custom-VJP boundary, so status is all-SUCCESS and stats empty --
    documented on the driver, and exactly the regime the serving layer's grad
    path uses."""
    leaves = jax.tree_util.tree_leaves(ys)
    b = leaves[0].shape[0]
    ts = jnp.broadcast_to(jnp.asarray(t_end, leaves[0].dtype), (b,))
    return Solution(ts=ts, ys=ys, status=jnp.zeros((b,), jnp.int32), stats={})


class _KeyedLRU:
    """The one keyed-LRU implementation behind both front-end caches
    (``CompiledSolver`` and ``sharded_solve``): a fix to keying or eviction
    applies to both or neither."""

    def __init__(self, maxsize: int):
        self.maxsize = maxsize
        self.data: OrderedDict = OrderedDict()
        self.hits = 0
        self.misses = 0

    def get(self, key):
        entry = self.data.get(key)
        if entry is not None:
            self.hits += 1
            self.data.move_to_end(key)
        else:
            self.misses += 1
        return entry

    def put(self, key, entry) -> None:
        self.data[key] = entry
        while len(self.data) > self.maxsize:
            self.data.popitem(last=False)

    def __len__(self) -> int:
        return len(self.data)

    def clear(self) -> None:
        self.data.clear()


class _CacheEntry:
    """One (static config, shapes) point of the solve cache.

    ``jitted`` is the jit-wrapped solve program: it traces exactly once (on
    the first call or on ``lower``) and later calls dispatch through jit's
    C++ fast path -- measurably faster than the Python call path of an
    ``XlaExecutable``.  ``executable`` is the AOT-compiled artifact, built
    lazily by ``CompiledSolver.compile``; once it exists, ``solve`` routes
    through it so an AOT-then-solve sequence never traces a second time.

    The cache key includes the tolerance-override shape class (see
    ``CompiledSolver._key``), so every call routed to this entry carries
    tolerance leaves matching the avals the entry was built for -- the
    executable is always usable when present.
    """

    __slots__ = ("jitted", "executable", "driver_leaves", "grad")

    def __init__(self, jitted, driver_leaves, grad: bool = False):
        self.jitted = jitted
        self.executable = None
        self.driver_leaves = driver_leaves
        self.grad = grad

    def call(self, y0, t_eval, t_start, t_end, dt0, args, rtol, atol,
             cotangent=None) -> Solution:
        tol_leaves = self.driver_leaves
        fn = self.executable if self.executable is not None else self.jitted
        if rtol is not None or atol is not None:
            tol_leaves = list(tol_leaves)
            if rtol is not None:
                tol_leaves[0] = rtol
            if atol is not None:
                tol_leaves[1] = atol
        if self.grad:
            return fn(y0, tol_leaves, t_eval, t_start, t_end, dt0, args, cotangent)
        return fn(y0, tol_leaves, t_eval, t_start, t_end, dt0, args)


class CompiledSolve:
    """A fully AOT-compiled solve program for one (static config, shapes)
    point.  Calling it never traces: the arguments' shapes/dtypes must match
    the specs it was compiled for (a mismatch raises instead of silently
    recompiling -- that is the point)."""

    def __init__(self, entry: _CacheEntry):
        self._entry = entry

    def __call__(
        self,
        y0,
        t_eval=None,
        *,
        t_start=None,
        t_end=None,
        dt0=None,
        args: Any = None,
        rtol=None,
        atol=None,
        cotangent=None,
    ) -> Solution:
        return self._entry.call(y0, t_eval, t_start, t_end, dt0, args, rtol,
                                atol, cotangent)

    def as_text(self) -> str:
        """The compiled program's HLO (donation shows up as input/output
        aliasing on the ``y0`` parameter)."""
        return self._entry.executable.as_text()


class CompiledSolver:
    """Zero-retrace front end over a loop driver.

    Example (serving loop)::

        solver = CompiledSolver(AutoDiffAdjoint(Stepper("dopri5")))
        for batch in requests:                       # same (b, f) shapes
            sol = solver.solve(f, batch.y0, t_eval)  # traces exactly once

    ``solve`` arguments and semantics match ``AutoDiffAdjoint.solve``; add
    per-call ``rtol``/``atol`` overrides (dynamic -- they never retrace when
    they keep the driver tolerances' shape/dtype; an override with a *new*
    shape, e.g. a per-instance vector over a scalar default, compiles one
    variant program on first use).  The cache key is ``(driver static config,
    f identity, shapes/dtypes of every dynamic argument)``; see the module
    docstring for the full static/dynamic contract and the ``donate`` caveat.
    """

    __setattr__ = frozen_setattr

    def __init__(
        self,
        solver: _Driver | AbstractStepper | str | None = None,
        *,
        donate: bool | str = "auto",
        cache_size: int = 128,
        **driver_kw,
    ):
        if donate not in (True, False, "auto"):
            raise ValueError(f"donate must be True, False or 'auto', got {donate!r}")
        if isinstance(solver, (_Driver, BacksolveAdjoint)):
            if driver_kw:
                raise TypeError("pass driver options to the driver, not CompiledSolver")
            driver = solver
        else:
            driver = AutoDiffAdjoint(AbstractStepper.coerce(solver), **driver_kw)
        self.driver = driver
        # BacksolveAdjoint is final-state-only (no t_eval/dt0); its forward
        # program wraps the returned y(t_end) in a synthesized Solution.
        self._backsolve = isinstance(driver, BacksolveAdjoint)
        self.donate = donate
        self.cache_size = cache_size
        self._cache = _KeyedLRU(cache_size)
        # The driver is frozen config: flatten it once and reuse on every call.
        leaves, treedef = jax.tree_util.tree_flatten(driver)
        self._driver_leaves = leaves
        self._driver_def = treedef
        self._driver_tol_keys = tuple(_leaf_key(x) for x in leaves)
        self._driver_key = (treedef, self._driver_tol_keys)
        freeze(self)

    def cache_info(self) -> CacheInfo:
        c = self._cache
        return CacheInfo(c.hits, c.misses, len(c), self.cache_size)

    def cache_clear(self) -> None:
        self._cache.clear()

    @staticmethod
    def _device_key(device):
        """Cache-key component of a placement request: ``None`` (default
        placement) and explicit devices key distinct entries, because an AOT
        executable is pinned to the device it lowered for -- one executable
        per device is exactly what lets a serving process round-robin
        concurrent buckets across the whole mesh."""
        return None if device is None else (device.platform, device.id)

    def _tol_key(self, x, i):
        """Shape class of a tolerance override: ``None`` when absent *or*
        when it matches the driver leaf's aval (same program either way --
        tolerances are dynamic leaves), a distinct key otherwise (e.g. a
        per-instance vector over a scalar default selects its own program
        point, which ``compile`` can AOT-build)."""
        if x is None:
            return None
        k = _leaf_key(x)
        return None if k == self._driver_tol_keys[i] else k

    def _validate(self, t_eval, dt0, cotangent) -> None:
        if self._backsolve and (t_eval is not None or dt0 is not None):
            raise TypeError(
                "BacksolveAdjoint tracks only the final state: pass "
                "t_start/t_end, not t_eval/dt0"
            )
        if cotangent is not None and isinstance(self.driver, AutoDiffAdjoint):
            raise TypeError(
                "AutoDiffAdjoint's while_loop has no reverse-mode rule: "
                "gradient programs (cotangent=...) need ScanAdjoint "
                "(discretize-then-optimize) or BacksolveAdjoint (adjoint ODE)"
            )

    def _key(self, f, y0, t_eval, t_start, t_end, dt0, args, rtol=None,
             atol=None, device=None, cotangent=None) -> tuple:
        return (
            self._driver_key,
            # The kernel backend is read at trace time: a program traced for
            # one backend must never serve a call made under another.
            ops.backend(),
            _f_key(f),
            _tree_key(y0),
            _tree_key(t_eval),
            _tree_key(t_start),
            _tree_key(t_end),
            _tree_key(dt0),
            _tree_key(args),
            self._tol_key(rtol, 0),
            self._tol_key(atol, 1),
            self._device_key(device),
            _tree_key(cotangent),
        )

    def cache_key(self, f, y0, t_eval=None, *, t_start=None, t_end=None,
                  dt0=None, args: Any = None, rtol=None, atol=None,
                  device=None, cotangent=None) -> tuple:
        """The hashable identity of the compiled program a ``solve`` with
        these arguments (or ``ShapeDtypeStruct`` specs) would dispatch to:
        (driver static config, kernel backend, dynamics identity, every
        dynamic argument's shape/dtype class, placement, cotangent class --
        ``None`` for forward programs).  Two argument sets with equal keys share one executable.
        The serving layer buckets requests by exactly this key, so a bucket
        never straddles two programs (and forward and gradient requests never
        share a bucket)."""
        self._validate(t_eval, dt0, cotangent)
        return self._key(f, y0, t_eval, t_start, t_end, dt0, args, rtol, atol,
                         device, cotangent)

    def _donate(self, t_eval) -> bool:
        """Resolve the donation policy: 'auto' donates y0 exactly when the
        solve tracks only the final state, the one case where an output buffer
        (ys, shaped like y0) exists for XLA to alias into."""
        if self.donate == "auto":
            return t_eval is None
        return self.donate

    def _build(self, f, t_eval, grad: bool = False) -> _CacheEntry:
        """Build the jit-wrapped solve program for one cache point.

        Forward programs call the driver directly.  Gradient programs
        (``grad=True``) wrap the driver's solve in ``jax.vjp`` over
        ``(y0, args)``, pull the caller's cotangent through it, and deliver
        the result as a ``Solution`` whose ``grads`` field carries
        ``Grads(y0=..., args=...)`` -- one compiled artifact per (config,
        shapes, device) covering forward AND backward, which is what makes a
        served gradient request prewarmable exactly like inference.
        """
        driver_def = self._driver_def
        backsolve = self._backsolve

        def run(drv, y0, t_eval, t_start, t_end, dt0, args) -> Solution:
            if backsolve:
                ys = drv.solve(f, y0, t_start=t_start, t_end=t_end, args=args)
                return _final_state_solution(ys, t_end)
            return drv.solve(
                f, y0, t_eval, t_start=t_start, t_end=t_end, dt0=dt0, args=args
            )

        if not grad:
            def fn(y0, tol_leaves, t_eval, t_start, t_end, dt0, args):
                drv = jax.tree_util.tree_unflatten(driver_def, tol_leaves)
                return run(drv, y0, t_eval, t_start, t_end, dt0, args)

            donate = (0,) if self._donate(t_eval) else ()
            return _CacheEntry(jax.jit(fn, donate_argnums=donate),
                               self._driver_leaves)

        def fn(y0, tol_leaves, t_eval, t_start, t_end, dt0, args, cotangent):
            drv = jax.tree_util.tree_unflatten(driver_def, tol_leaves)

            def fwd(y0_, args_):
                sol = run(drv, y0_, t_eval, t_start, t_end, dt0, args_)
                return sol.ys, sol

            if args is None:
                # No args operand: keep the VJP arity minimal (and the
                # gradient None, distinguishable from a zero cotangent).
                ys, vjp_fn, sol = jax.vjp(lambda y_: fwd(y_, None), y0,
                                          has_aux=True)
                (gy0,) = vjp_fn(cotangent)
                gargs = None
            else:
                ys, vjp_fn, sol = jax.vjp(fwd, y0, args, has_aux=True)
                gy0, gargs = vjp_fn(cotangent)
            return dataclasses.replace(sol, grads=Grads(y0=gy0, args=gargs))

        # In the final-state regime the cotangent buffer (argnum 7) has the
        # same shape as ys and grads.y0, so XLA can alias it; y0 itself is a
        # VJP residual and must stay alive.
        donate = (7,) if self._donate(t_eval) else ()
        return _CacheEntry(jax.jit(fn, donate_argnums=donate),
                           self._driver_leaves, grad=True)

    def _lookup(self, f, y0, t_eval, t_start, t_end, dt0, args,
                rtol=None, atol=None, device=None, cotangent=None) -> _CacheEntry:
        self._validate(t_eval, dt0, cotangent)
        key = self._key(f, y0, t_eval, t_start, t_end, dt0, args, rtol, atol,
                        device, cotangent)
        entry = self._cache.get(key)
        if entry is None:
            entry = self._build(f, t_eval, grad=cotangent is not None)
            self._cache.put(key, entry)
        return entry

    def compile(
        self,
        f,
        y0,
        t_eval=None,
        *,
        t_start=None,
        t_end=None,
        dt0=None,
        args: Any = None,
        rtol=None,
        atol=None,
        device=None,
        cotangent=None,
    ) -> CompiledSolve:
        """AOT-compile for the given argument specs (``jax.ShapeDtypeStruct``
        or example arrays) and return the callable executable handle.  The
        entry is also installed in the cache, so a later ``solve`` with
        matching shapes dispatches to the same executable without ever
        tracing again.

        ``rtol``/``atol`` specs select the tolerance shape class to build:
        pass e.g. ``jax.ShapeDtypeStruct((b,), jnp.float32)`` to AOT-compile
        the per-instance-tolerance variant a serving bucket will call with
        (omitting them compiles the driver-default class).

        ``cotangent`` specs (matching the output ``ys``) AOT-build the
        *gradient* program for this point: the VJP-wrapped solve that
        ``solve(..., cotangent=...)`` dispatches to.  Gradient and forward
        programs are distinct cache entries.

        ``device`` pins the executable to one device of the mesh (every
        dynamic argument must then live there at call time -- ``solve`` with
        the same ``device`` places them).  Each device compiles its own
        entry; the serving layer prewarms one per device it round-robins
        over."""
        entry = self._lookup(f, y0, t_eval, t_start, t_end, dt0, args, rtol,
                             atol, device, cotangent)
        if entry.executable is None:
            tol_leaves = list(self._driver_leaves)
            if rtol is not None:
                tol_leaves[0] = rtol
            if atol is not None:
                tol_leaves[1] = atol
            spec_of = _spec
            if device is not None:
                from jax.sharding import SingleDeviceSharding

                sharding = SingleDeviceSharding(device)
                spec_of = lambda x: jax.ShapeDtypeStruct(
                    _spec(x).shape, _spec(x).dtype, sharding=sharding
                )
            operands = (y0, tol_leaves, t_eval, t_start, t_end, dt0, args)
            if entry.grad:
                operands = operands + (cotangent,)
            abstract = jax.tree_util.tree_map(spec_of, operands)
            entry.executable = entry.jitted.lower(*abstract).compile()
        return CompiledSolve(entry)

    def prewarm(self, f, specs: "list[dict] | tuple[dict, ...]") -> int:
        """AOT-compile a batch of program points before traffic arrives.

        Each element of ``specs`` is a kwargs mapping for :meth:`compile`
        minus ``f`` (so it must carry ``y0`` plus whichever of ``t_eval``/
        ``t_start``/``t_end``/``dt0``/``args``/``rtol``/``atol``/``device``
        the serving call will pass), with ``jax.ShapeDtypeStruct`` leaves
        standing in for the concrete arrays.  Returns the number of entries
        compiled for the first time (already-warm points are skipped for
        free, so prewarming is idempotent)."""
        n_new = 0
        for spec in specs:
            spec = dict(spec)
            kw = {k: spec.pop(k, None)
                  for k in ("t_eval", "t_start", "t_end", "dt0", "args",
                            "rtol", "atol", "device", "cotangent")}
            y0 = spec.pop("y0")
            if spec:
                raise TypeError(f"unknown prewarm spec keys: {sorted(spec)}")
            key = self._key(f, y0, kw["t_eval"], kw["t_start"], kw["t_end"],
                            kw["dt0"], kw["args"], kw["rtol"], kw["atol"],
                            kw["device"], kw["cotangent"])
            entry = self._cache.data.get(key)
            if entry is not None and entry.executable is not None:
                continue
            self.compile(f, y0, **kw)
            n_new += 1
        return n_new

    def solve(
        self,
        f,
        y0,
        t_eval=None,
        *,
        t_start=None,
        t_end=None,
        dt0=None,
        args: Any = None,
        rtol=None,
        atol=None,
        device=None,
        cotangent=None,
    ) -> Solution:
        """Dispatch a solve through the zero-retrace cache.  ``device``
        selects the per-device program variant (see :meth:`compile`) and
        commits every dynamic argument there first -- a no-op transfer for
        arguments the caller already placed, which is the serving fast path
        (the batch packer lands buffers on the target device directly).

        ``cotangent`` (matching the output ``ys``; usually ``ones_like`` of
        the final state, or the loss gradient w.r.t. it) routes through the
        *gradient* program: the returned ``Solution`` additionally carries
        ``grads = Grads(y0=dL/dy0, args=dL/dargs)``.  Requires a
        reverse-differentiable driver (``ScanAdjoint``/``BacksolveAdjoint``)."""
        if device is not None:
            (y0, t_eval, t_start, t_end, dt0, args, rtol, atol,
             cotangent) = jax.device_put(
                (y0, t_eval, t_start, t_end, dt0, args, rtol, atol, cotangent),
                device,
            )
        entry = self._lookup(f, y0, t_eval, t_start, t_end, dt0, args, rtol,
                             atol, device, cotangent)
        return entry.call(y0, t_eval, t_start, t_end, dt0, args, rtol, atol,
                          cotangent)


# --------------------------------------------------------------------------
# Multi-device sharding: the batch axis across a mesh.

_SHARDED_CACHE = _KeyedLRU(64)


def _batch_spec(x, batch: int, axis_name: str):
    """Shard any leaf whose leading dim is the batch axis; replicate the rest."""
    from jax.sharding import PartitionSpec as P

    s = _spec(x)
    if len(s.shape) >= 1 and s.shape[0] == batch:
        return P(axis_name)
    return P()


def sharded_solve(
    mesh,
    f,
    y0,
    t_eval=None,
    *,
    t_start=None,
    t_end=None,
    dt0=None,
    args: Any = None,
    solver: _Driver | None = None,
    method: AbstractStepper | str | None = None,
    rtol=None,
    atol=None,
    axis_name: str = "data",
    **solver_kw,
) -> Solution:
    """Solve a batch of IVPs with the batch axis sharded across ``mesh``.

    Instances are independent by the solver's core contract, so this is
    embarrassingly parallel: each device runs the complete adaptive loop on
    its ``b / n_devices`` shard, terminating on its *local* all-done
    reduction (a device whose shard finishes early goes idle instead of
    lock-stepping with the stragglers -- strictly less overhanging work than
    the single-device program).  For explicit steppers, per-instance results,
    statuses and stats are bitwise identical to the single-device ``jax.jit``
    program.  Two caveats: whole-batch overhang accounting (``n_f_evals``)
    can differ, because the dynamics stop being evaluated for a shard as soon
    as that shard drains; and the implicit steppers' batched linear algebra
    compiles to batch-size-dependent XLA fusions, so their agreement is at
    rounding level rather than bitwise.

    Sharding rule: ``y0`` leaves, ``(b,)``-shaped ``t_start``/``t_end``/
    ``dt0``/tolerances, 2-D ``(b, n)`` ``t_eval`` and any ``args`` leaf whose
    leading dim equals the batch size shard along ``axis_name``; everything
    else is replicated (1-D ``t_eval`` is always replicated -- it is a shared
    time grid, whatever its length).

    The batch does NOT have to divide the mesh: a ragged batch is padded up
    to the next multiple of the mesh axis with copies of instance 0 (the
    same trick the serving layer uses for bucket padding -- instances never
    interact, so pad rows only cost FLOPs), solved, and sliced back, so the
    returned ``Solution`` covers exactly the ``b`` requested instances and
    every real instance matches the unsharded program.  A serve-time hot
    bucket can therefore span the mesh whatever its size.

    Pass a configured driver via ``solver=`` or let ``method``/``rtol``/
    ``atol``/``solver_kw`` build an ``AutoDiffAdjoint``.  The shard-mapped
    program is jitted and cached, so repeated same-shape calls do not retrace.
    """
    from jax.sharding import PartitionSpec as P

    if solver is None:
        solver = AutoDiffAdjoint(
            AbstractStepper.coerce(method),
            rtol=1e-3 if rtol is None else rtol,
            atol=1e-6 if atol is None else atol,
            **solver_kw,
        )
    elif method is not None or rtol is not None or atol is not None or solver_kw:
        raise TypeError(
            "pass solver options (method/rtol/atol/...) to the driver given "
            "via solver=, not to sharded_solve"
        )

    # Commit every leaf to a device array: the sharding specs below are
    # computed from concrete shapes, and host scalars must not split the key.
    y0, t_eval, t_start, t_end, dt0, args = jax.tree_util.tree_map(
        jnp.asarray, (y0, t_eval, t_start, t_end, dt0, args)
    )
    y0_leaves = jax.tree_util.tree_leaves(y0)
    if not y0_leaves:
        raise ValueError("y0 has no array leaves")
    requested = y0_leaves[0].shape[0]
    n_dev = mesh.shape[axis_name]
    n_pad = (-requested) % n_dev

    driver_leaves, driver_def = jax.tree_util.tree_flatten(solver)
    inputs = (driver_leaves, y0, t_eval, t_start, t_end, dt0, args)

    if n_pad:
        # Ragged batch: pad every batch-leading leaf (the same leaves the
        # sharding rule below would shard) to the next multiple of the mesh
        # axis by replicating instance 0, and slice the padding back off the
        # result.  The 1-D t_eval exception mirrors spec_for: a shared grid
        # is never a batch axis, whatever its length.
        def pad_tree(tree):
            if tree is t_eval and t_eval is not None and jnp.ndim(t_eval) == 1:
                return tree
            return jax.tree_util.tree_map(
                lambda x: jnp.concatenate(
                    [x, jnp.repeat(x[:1], n_pad, axis=0)], axis=0)
                if jnp.ndim(x) >= 1 and x.shape[0] == requested else x,
                tree,
            )

        driver_leaves, y0, t_eval, t_start, t_end, dt0, args = (
            pad_tree(tree) for tree in inputs
        )
        inputs = (driver_leaves, y0, t_eval, t_start, t_end, dt0, args)
    batch = requested + n_pad

    key = (
        mesh, axis_name, driver_def, ops.backend(), _f_key(f),
        tuple(_tree_key(t) for t in inputs),
    )
    entry = _SHARDED_CACHE.get(key)
    if entry is None:
        def spec_for(tree):
            if tree is t_eval and t_eval is not None and jnp.ndim(t_eval) == 1:
                return P()  # shared time grid, even if its length equals the batch
            return jax.tree_util.tree_map(
                lambda x: _batch_spec(x, batch, axis_name), tree
            )

        in_specs = tuple(spec_for(tree) for tree in inputs)

        def local(driver_leaves, y0, t_eval, t_start, t_end, dt0, args):
            drv = jax.tree_util.tree_unflatten(driver_def, driver_leaves)
            return drv.solve(
                f, y0, t_eval, t_start=t_start, t_end=t_end, dt0=dt0, args=args
            )

        out_shape = jax.eval_shape(local, *inputs)
        out_specs = jax.tree_util.tree_map(lambda _: P(axis_name), out_shape)
        entry = jax.jit(
            jax.shard_map(
                local, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                check_vma=False,
            )
        )
        _SHARDED_CACHE.put(key, entry)
    sol = entry(*inputs)
    return sol.slice_batch(slice(0, requested)) if n_pad else sol
