"""ODE integration: ``solve_ivp`` with RK23 / RK45 / DOP853.

Reference analog: ``sparse/integrate.py`` (1824 LoC) — a scipy-style IVP
solver stack (OdeSolver integrate.py:204, RK23 :750, RK45 :838, DOP853 :987,
solve_ivp :1303, dense outputs, event handling) whose inner RK stage update
``dy = h * K[:s].T @ a`` is fused into the RK_CALC_DY task
(integrate.py:478-494, ``src/sparse/integrate/runge_kutta.*``).

TPU-first redesign: the state vector ``y`` and every stage live on device;
all stage math for one step attempt — the K evaluations, the candidate
``y_new``, the embedded error estimate — is a single jitted closure, so the
RK_CALC_DY fusion is subsumed by XLA (the stage contraction is an [s, n]
matvec, MXU-shaped for wide systems). The adaptive step-size controller is
O(1) host scalar work, synced once per step attempt on the error norm — the
same control/device boundary the reference blocks on. Complex-valued systems
(the quantum evolution workload, §3.5) are supported natively.
"""

from __future__ import annotations

import numbers

import jax
import jax.numpy as jnp
import numpy as np

from . import dop853_coefficients
from .utils import asjnp, in_trace

SAFETY = 0.9
MIN_FACTOR = 0.2
MAX_FACTOR = 10.0
EPS = np.finfo(float).eps


def _jit_with_eager_fallback(core):
    """jit `core`, but fall back to eager if the user RHS isn't traceable.

    The RHS is user code; numpy-based functions (scipy-style) raise trace
    errors under jit, so those run the same math eagerly (device arrays,
    op-by-op) — still correct, just without whole-step fusion.
    """
    jcore = jax.jit(core)
    state = {"use_jit": True}

    def wrapper(*a):
        if state["use_jit"]:
            try:
                return jcore(*a)
            except (
                jax.errors.TracerArrayConversionError,
                jax.errors.TracerBoolConversionError,
                jax.errors.TracerIntegerConversionError,
                jax.errors.ConcretizationTypeError,
            ):
                state["use_jit"] = False
        return core(*a)

    return wrapper


def _wrap_fun(fun, args):
    """Bind args and route standalone RHS calls through jit.

    The solver's hot loop compiles the whole RK step (``_build_step_core``),
    but the setup path (initial f, first-step selection) and any eager
    fallback call ``fun`` directly. Eager elementwise arithmetic in a user
    RHS would dispatch (and compile) op by op on an accelerator, so the
    standalone calls are jitted too, with
    ``t`` passed as a 0-d array so changing times never retrace. Inside an
    active trace (the step core) the raw callable is used directly, and a
    non-traceable (numpy-based) RHS falls back to eager per-call.
    """
    if args:
        def raw(t, y):
            return asjnp(fun(t, y, *args))
    else:
        def raw(t, y):
            return asjnp(fun(t, y))

    jraw = jax.jit(raw)
    state = {"use_jit": True}
    tdt = np.float64 if jax.config.jax_enable_x64 else np.float32

    def wrapped(t, y):
        if in_trace():
            return raw(t, y)
        if state["use_jit"]:
            try:
                return jraw(np.asarray(t, dtype=tdt), y)
            except (
                jax.errors.TracerArrayConversionError,
                jax.errors.TracerBoolConversionError,
                jax.errors.TracerIntegerConversionError,
                jax.errors.ConcretizationTypeError,
            ):
                state["use_jit"] = False
        return raw(t, y)

    # identity anchor for the step-core cache: repeated solves over the
    # SAME user RHS (warm-up solve then timed solve) must reuse the same
    # compiled core even though each solve_ivp builds a fresh wrapper
    # Only VALUE-typed args may key the cache. Anything with a mutable
    # numeric payload (ndarray, jax array, sparse matrix — the common
    # solve_ivp(f, span, y0, args=(A,)) pattern) must NOT: hashability is
    # no safeguard (sparse matrices hash by identity), and an identity-
    # keyed hit would silently serve a core with the OLD values baked in
    # as trace constants after an in-place `A.data *= 2` between solves.
    # Such solves retrace instead (scipy-parity cost, correctness first).
    def value_typed(a):
        if isinstance(a, (numbers.Number, str, bytes, type(None))):
            return True
        if isinstance(a, (tuple, frozenset)):
            return all(value_typed(x) for x in a)
        return False

    if all(value_typed(a) for a in args):
        wrapped._cache_key = (fun, tuple(args))
    return wrapped


def validate_max_step(max_step):
    if max_step <= 0:
        raise ValueError("`max_step` must be positive.")
    return max_step


def validate_tol(rtol, atol, n):
    if rtol < 100 * EPS:
        rtol = 100 * EPS
    atol = np.asarray(atol)
    if atol.ndim > 0 and atol.shape != (n,):
        raise ValueError("`atol` has wrong shape.")
    if np.any(atol < 0):
        raise ValueError("`atol` must be positive.")
    return rtol, atol


def _axpy_jit(y, a, f):
    return y + a * f


_axpy = jax.jit(_axpy_jit)


def select_initial_step(fun, t0, y0, f0, direction, order, rtol, atol):
    """Empirical first-step selection (Hairer et al., as in scipy).

    The y1 probe runs through a jitted axpy: this is the one eager device
    op in the solver setup path, and compiled it is one dispatch. The
    step scalar is passed as a numpy value so h0 changes don't retrace.
    """
    if y0.shape[0] == 0:
        return np.inf
    y0_h = np.asarray(y0)
    f0_h = np.asarray(f0)
    scale = atol + np.abs(y0_h) * rtol
    d0 = float(np.linalg.norm(y0_h / scale) / np.sqrt(y0.shape[0]))
    d1 = float(np.linalg.norm(f0_h / scale) / np.sqrt(y0.shape[0]))
    h0 = 1e-6 if d0 < 1e-5 or d1 < 1e-5 else 0.01 * d0 / d1
    y1 = _axpy(y0, np.asarray(h0 * direction, dtype=f0_h.real.dtype), f0)
    f1 = fun(t0 + h0 * direction, y1)
    d2 = (
        float(np.linalg.norm((np.asarray(f1) - f0_h) / scale) / np.sqrt(y0.shape[0]))
        / h0
    )
    if d1 <= 1e-15 and d2 <= 1e-15:
        h1 = max(1e-6, h0 * 1e-3)
    else:
        h1 = (0.01 / max(d1, d2)) ** (1.0 / (order + 1))
    return min(100 * h0, h1)


class OdeSolver:
    """Base solver protocol (reference integrate.py:204)."""

    TOO_SMALL_STEP = "Required step size is less than spacing between numbers."

    def __init__(self, fun, t0, y0, t_bound, vectorized=False, support_complex=True):
        self.t = t0
        self.t_old = None
        self.y = asjnp(y0)
        if np.issubdtype(self.y.dtype, np.integer):
            self.y = self.y.astype(
                jnp.float64 if jax.config.jax_enable_x64 else jnp.float32
            )
        self.t_bound = t_bound
        self.vectorized = vectorized
        if vectorized:
            base = fun

            def fun_single(t, y):
                return asjnp(base(t, y[:, None]))[:, 0]

            self.fun = fun_single
        else:
            self.fun = fun
        self.direction = np.sign(t_bound - t0) if t_bound != t0 else 1
        self.n = self.y.shape[0]
        self.status = "running"
        self.nfev = 0
        self.njev = 0
        self.nlu = 0

    @property
    def step_size(self):
        if self.t_old is None:
            return None
        return abs(self.t - self.t_old)

    def step(self):
        if self.status != "running":
            raise RuntimeError("Attempt to step on a failed or finished solver.")
        if self.n == 0 or self.t == self.t_bound:
            self.t_old = self.t
            self.t = self.t_bound
            self.status = "finished"
            return None
        t = self.t
        success, message = self._step_impl()
        if not success:
            self.status = "failed"
            return message
        self.t_old = t
        if self.direction * (self.t - self.t_bound) >= 0:
            self.status = "finished"
        return None

    def dense_output(self):
        if self.t_old is None:
            raise RuntimeError("Dense output is available after a successful step was made.")
        if self.n == 0 or self.t == self.t_old:
            return ConstantDenseOutput(self.t_old, self.t, self.y)
        return self._dense_output_impl()


class RungeKutta(OdeSolver):
    """Explicit embedded Runge-Kutta base (reference integrate.py:593-750)."""

    C: np.ndarray
    A: np.ndarray
    B: np.ndarray
    E: np.ndarray
    P: np.ndarray
    order: int
    error_estimator_order: int
    n_stages: int

    def __init__(
        self,
        fun,
        t0,
        y0,
        t_bound,
        max_step=np.inf,
        rtol=1e-3,
        atol=1e-6,
        vectorized=False,
        first_step=None,
        **extraneous,
    ):
        super().__init__(fun, t0, y0, t_bound, vectorized, support_complex=True)
        self.max_step = validate_max_step(max_step)
        self.rtol, self.atol = validate_tol(rtol, atol, self.n)
        self.f = self.fun(self.t, self.y)
        self.nfev += 1
        if first_step is None:
            self.h_abs = select_initial_step(
                self.fun,
                t0,
                self.y,
                self.f,
                self.direction,
                self.error_estimator_order,
                self.rtol,
                self.atol,  # full (possibly per-component) tolerances
            )
            self.nfev += 1
        else:
            if first_step <= 0 or first_step > abs(t_bound - t0):
                raise ValueError("`first_step` has wrong magnitude.")
            self.h_abs = float(first_step)
        self.K = None
        self.error_exponent = -1.0 / (self.error_estimator_order + 1)
        self._step_core = self._build_step_core()

    # -- the fused, jitted step attempt (RK_CALC_DY analog) ----------------
    _STEP_CORE_CACHE: dict = {}

    def _build_step_core(self):
        # reuse the compiled core across solver instances for the same
        # (user fun, shapes, dtype, tolerances): a warm-up solve then
        # pays the trace/compile ONCE even without a persistent disk
        # cache — fresh jax.jit instances never share compilations
        ukey = getattr(self.fun, "_cache_key", None)
        ckey = None
        if ukey is not None:
            ckey = (
                type(self), ukey, self.y.shape, str(self.y.dtype),
                float(self.rtol), np.asarray(self.atol).tobytes(),
            )
            cached = RungeKutta._STEP_CORE_CACHE.get(ckey)
            if cached is not None:
                return cached
        core = self._build_step_core_uncached()
        if ckey is not None:
            cache = RungeKutta._STEP_CORE_CACHE
            if len(cache) > 32:  # bound: long test sessions, many RHSs
                cache.pop(next(iter(cache)))
            cache[ckey] = core
        return core

    def _build_step_core_uncached(self):
        A = self.A
        B = jnp.asarray(self.B)
        C = self.C
        E = jnp.asarray(self.E)
        n_stages = self.n_stages
        fun = self.fun
        rtol = self.rtol
        atol = self.atol

        def core(t, h, y, f):
            Ks = [f]
            for s in range(1, n_stages):
                a = A[s, :s]
                # dy = h * K[:s].T @ a — the RK_CALC_DY contraction, fused by XLA
                dy = h * sum(
                    aj * Kj for aj, Kj in zip(a, Ks) if aj != 0
                )
                Ks.append(fun(t + C[s] * h, y + dy))
            K = jnp.stack(Ks)  # [n_stages, n]
            y_new = y + h * (B @ K)
            f_new = fun(t + h, y_new)
            K_full = jnp.concatenate([K, f_new[None]])  # FSAL row
            err = h * (E @ K_full)
            scale = atol + jnp.maximum(jnp.abs(y), jnp.abs(y_new)) * rtol
            error_norm = jnp.sqrt(
                jnp.mean(jnp.abs(err / scale) ** 2)
            ) if y.shape[0] else jnp.zeros(())
            return y_new, f_new, K_full, error_norm

        return _jit_with_eager_fallback(core)

    def _step_impl(self):
        t = self.t
        max_step = self.max_step
        min_step = 10 * abs(np.nextafter(t, self.direction * np.inf) - t)
        h_abs = min(max(self.h_abs, min_step), max_step)

        step_accepted = False
        step_rejected = False
        while not step_accepted:
            if h_abs < min_step:
                return False, self.TOO_SMALL_STEP
            h = h_abs * self.direction
            t_new = t + h
            if self.direction * (t_new - self.t_bound) > 0:
                t_new = self.t_bound
            h = t_new - t
            h_abs = abs(h)
            y_new, f_new, K, error_norm = self._step_core(t, h, self.y, self.f)
            # core evaluates fun at stages 1..n_stages-1 plus f_new
            self.nfev += self.n_stages
            error_norm = float(error_norm)
            if error_norm < 1:
                factor = (
                    MAX_FACTOR
                    if error_norm == 0
                    else min(MAX_FACTOR, SAFETY * error_norm**self.error_exponent)
                )
                if step_rejected:
                    factor = min(1.0, factor)
                h_abs *= factor
                step_accepted = True
            else:
                h_abs *= max(MIN_FACTOR, SAFETY * error_norm**self.error_exponent)
                step_rejected = True

        self.h_previous = h
        self.y_old = self.y
        self.t = t_new
        self.y = y_new
        self.h_abs = h_abs
        self.f = f_new
        self.K = K
        return True, None

    def _dense_output_impl(self):
        Q = self.K.T @ jnp.asarray(self.P, dtype=self.K.dtype)
        return RkDenseOutput(self.t_old, self.t, self.y_old, Q)


class RK23(RungeKutta):
    """Bogacki-Shampine 3(2) pair (reference integrate.py:750)."""

    order = 3
    error_estimator_order = 2
    n_stages = 3
    C = np.array([0, 1 / 2, 3 / 4])
    A = np.array([[0, 0, 0], [1 / 2, 0, 0], [0, 3 / 4, 0]])
    B = np.array([2 / 9, 1 / 3, 4 / 9])
    E = np.array([5 / 72, -1 / 12, -1 / 9, 1 / 8])
    P = np.array(
        [[1, -4 / 3, 5 / 9], [0, 1, -2 / 3], [0, 4 / 3, -8 / 9], [0, -1, 1]]
    )


class RK45(RungeKutta):
    """Dormand-Prince 5(4) pair (reference integrate.py:838)."""

    order = 5
    error_estimator_order = 4
    n_stages = 6
    C = np.array([0, 1 / 5, 3 / 10, 4 / 5, 8 / 9, 1])
    A = np.array(
        [
            [0, 0, 0, 0, 0],
            [1 / 5, 0, 0, 0, 0],
            [3 / 40, 9 / 40, 0, 0, 0],
            [44 / 45, -56 / 15, 32 / 9, 0, 0],
            [19372 / 6561, -25360 / 2187, 64448 / 6561, -212 / 729, 0],
            [9017 / 3168, -355 / 33, 46732 / 5247, 49 / 176, -5103 / 18656],
        ]
    )
    B = np.array([35 / 384, 0, 500 / 1113, 125 / 192, -2187 / 6784, 11 / 84])
    E = np.array(
        [71 / 57600, 0, -71 / 16695, 71 / 1920, -17253 / 339200, 22 / 525, -1 / 40]
    )
    P = np.array(
        [
            [1, -8048581381 / 2820520608, 8663915743 / 2820520608, -12715105075 / 11282082432],
            [0, 0, 0, 0],
            [0, 131558114200 / 32700410799, -68118460800 / 10900136933, 87487479700 / 32700410799],
            [0, -1754552775 / 470086768, 14199869525 / 1410260304, -10690763975 / 1880347072],
            [0, 127303824393 / 49829197408, -318862633887 / 49829197408, 701980252875 / 199316789632],
            [0, -282668133 / 205662961, 2019193451 / 616988883, -1453857185 / 822651844],
            [0, 40617522 / 29380423, -110615467 / 29380423, 69997945 / 29380423],
        ]
    )


class DOP853(RungeKutta):
    """Hairer's 8(5,3) method with 7th-order dense output (integrate.py:987)."""

    n_stages = dop853_coefficients.N_STAGES
    order = 8
    error_estimator_order = 7
    A = dop853_coefficients.A[:n_stages, :n_stages]
    B = dop853_coefficients.B
    C = dop853_coefficients.C[:n_stages]
    E3 = dop853_coefficients.E3
    E5 = dop853_coefficients.E5
    D = dop853_coefficients.D
    A_EXTRA = dop853_coefficients.A[n_stages + 1 :]
    C_EXTRA = dop853_coefficients.C[n_stages + 1 :]
    E = None  # error handled by the 5-3 pair below

    def _build_step_core_uncached(self):
        A = self.A
        B = jnp.asarray(self.B)
        C = self.C
        E3 = jnp.asarray(self.E3)
        E5 = jnp.asarray(self.E5)
        n_stages = self.n_stages
        fun = self.fun
        rtol = self.rtol
        atol = self.atol

        def core(t, h, y, f):
            Ks = [f]
            for s in range(1, n_stages):
                a = A[s, :s]
                dy = h * sum(aj * Kj for aj, Kj in zip(a, Ks) if aj != 0)
                Ks.append(fun(t + C[s] * h, y + dy))
            K = jnp.stack(Ks)
            y_new = y + h * (B @ K)
            f_new = fun(t + h, y_new)
            K_full = jnp.concatenate([K, f_new[None]])
            scale = atol + jnp.maximum(jnp.abs(y), jnp.abs(y_new)) * rtol
            err5 = (E5 @ K_full) / scale
            err3 = (E3 @ K_full) / scale
            err5n2 = jnp.sum(jnp.abs(err5) ** 2)
            err3n2 = jnp.sum(jnp.abs(err3) ** 2)
            denom = err5n2 + 0.01 * err3n2
            nn = max(y.shape[0], 1)
            error_norm = jnp.abs(h) * err5n2 / jnp.sqrt(
                jnp.where(denom == 0, 1.0, denom) * nn
            )
            error_norm = jnp.where(denom > 0, error_norm, jnp.zeros(()))
            return y_new, f_new, K_full, error_norm

        return _jit_with_eager_fallback(core)

    def _dense_output_impl(self):
        """Extended-stage 7th-order interpolant (scipy-compatible)."""
        K = self.K  # [n_stages + 1, n]
        h = self.h_previous
        t_old = self.t_old
        fun = self.fun
        Ks_ext = list(K)
        for s_ext, (a, c) in enumerate(zip(self.A_EXTRA, self.C_EXTRA)):
            s = self.n_stages + 1 + s_ext
            dy = h * sum(
                float(aj) * Kj for aj, Kj in zip(a[:s], Ks_ext) if aj != 0
            )
            Ks_ext.append(fun(t_old + c * h, self.y_old + dy))
            self.nfev += 1
        K_ext = jnp.stack(Ks_ext)  # [N_STAGES_EXTENDED, n]
        D = jnp.asarray(self.D, dtype=K_ext.dtype)
        F = jnp.zeros(
            (dop853_coefficients.INTERPOLATOR_POWER, self.n), dtype=K_ext.dtype
        )
        f_old = K[0]
        delta_y = self.y - self.y_old
        F = F.at[0].set(delta_y)
        F = F.at[1].set(h * f_old - delta_y)
        F = F.at[2].set(2 * delta_y - h * (self.f + f_old))
        F = F.at[3:].set(h * (D @ K_ext))
        return Dop853DenseOutput(self.t_old, self.t, self.y_old, F)


# ---------------------------------------------------------------------------
# Dense outputs
# ---------------------------------------------------------------------------
class DenseOutput:
    def __init__(self, t_old, t):
        self.t_old = t_old
        self.t = t
        self.t_min = min(t, t_old)
        self.t_max = max(t, t_old)

    def __call__(self, t):
        t = np.asarray(t)
        if t.ndim > 1:
            raise ValueError("`t` must be a float or a 1-D array.")
        return self._call_impl(t)


class ConstantDenseOutput(DenseOutput):
    def __init__(self, t_old, t, value):
        super().__init__(t_old, t)
        self.value = value

    def _call_impl(self, t):
        if t.ndim == 0:
            return self.value
        return jnp.repeat(self.value[:, None], t.shape[0], axis=1)


class RkDenseOutput(DenseOutput):
    def __init__(self, t_old, t, y_old, Q):
        super().__init__(t_old, t)
        self.h = t - t_old
        self.Q = Q
        self.order = Q.shape[1] - 1
        self.y_old = y_old

    def _call_impl(self, t):
        x = (t - self.t_old) / self.h
        if t.ndim == 0:
            p = np.cumprod(np.tile(x, self.order + 1))
            y = self.h * (self.Q @ jnp.asarray(p, dtype=self.Q.dtype))
            return self.y_old + y
        p = np.cumprod(np.tile(x, (self.order + 1, 1)), axis=0)
        y = self.h * (self.Q @ jnp.asarray(p, dtype=self.Q.dtype))
        return self.y_old[:, None] + y


class Dop853DenseOutput(DenseOutput):
    def __init__(self, t_old, t, y_old, F):
        super().__init__(t_old, t)
        self.h = t - t_old
        self.F = F
        self.y_old = y_old

    def _call_impl(self, t):
        x = (t - self.t_old) / self.h
        if t.ndim == 0:
            y = jnp.zeros_like(self.y_old)
            for i, f in enumerate(reversed(list(self.F))):
                y = y + f
                y = y * (x if i % 2 == 0 else (1 - x))
            return y + self.y_old
        x = x[None, :]
        y = jnp.zeros((self.y_old.shape[0], t.shape[0]), dtype=self.y_old.dtype)
        xj = jnp.asarray(x, dtype=jnp.result_type(self.y_old.dtype, float))
        for i, f in enumerate(reversed(list(self.F))):
            y = y + f[:, None]
            y = y * (xj if i % 2 == 0 else (1 - xj))
        return y + self.y_old[:, None]


class OdeSolution:
    """Piecewise dense-output spline collection (scipy-compatible)."""

    def __init__(self, ts, interpolants):
        self.ts = np.asarray(ts)
        self.interpolants = interpolants
        d = np.diff(self.ts)
        self.ascending = np.all(d >= 0)
        self.t_min = self.ts[0] if self.ascending else self.ts[-1]
        self.t_max = self.ts[-1] if self.ascending else self.ts[0]

    def _segment(self, t):
        ts = self.ts if self.ascending else self.ts[::-1]
        i = np.clip(np.searchsorted(ts, t, side="left") - 1, 0, len(self.interpolants) - 1)
        if not self.ascending:
            i = len(self.interpolants) - 1 - i
        return int(i)

    def __call__(self, t):
        t = np.asarray(t, dtype=float)
        if t.ndim == 0:
            return self.interpolants[self._segment(t)](t)
        # group consecutive query points by segment: one batched interpolant
        # evaluation per segment instead of one dispatch per point
        segs = np.array([self._segment(tv) for tv in t])
        cols = []
        i = 0
        while i < t.shape[0]:
            j = i
            while j < t.shape[0] and segs[j] == segs[i]:
                j += 1
            cols.append(self.interpolants[segs[i]](t[i:j]))
            i = j
        return jnp.concatenate(cols, axis=1)


# ---------------------------------------------------------------------------
# Event handling
# ---------------------------------------------------------------------------
def prepare_events(events, args=()):
    if callable(events):
        events = (events,)
    if events is None:
        return None, None, None
    is_terminal = np.empty(len(events), dtype=bool)
    direction = np.empty(len(events))
    wrapped = []
    for i, event in enumerate(events):
        is_terminal[i] = bool(getattr(event, "terminal", False))
        direction[i] = getattr(event, "direction", 0)
        if args:
            # scipy contract: events receive the same extra args as fun
            wrapped.append(lambda t, y, event=event: event(t, y, *args))
        else:
            wrapped.append(event)
    return wrapped, is_terminal, direction


def solve_event_equation(event, sol, t_old, t):
    from scipy.optimize import brentq

    return brentq(
        lambda tt: float(np.asarray(event(tt, sol(tt)))), t_old, t, xtol=4 * EPS, rtol=4 * EPS
    )


def find_active_events(g, g_new, direction):
    g, g_new = np.asarray(g), np.asarray(g_new)
    up = (g <= 0) & (g_new >= 0)
    down = (g >= 0) & (g_new <= 0)
    either = up | down
    mask = (
        (up & (direction > 0))
        | (down & (direction < 0))
        | (either & (direction == 0))
    )
    return np.nonzero(mask)[0]


def handle_events(sol, events, active_events, is_terminal, t_old, t):
    roots = np.asarray(
        [solve_event_equation(events[e], sol, t_old, t) for e in active_events]
    )
    if np.any(is_terminal[active_events]):
        order = np.argsort(np.sign(t - t_old) * roots)
        active_events = active_events[order]
        roots = roots[order]
        tmask = is_terminal[active_events]
        stop = np.nonzero(tmask)[0][0]
        active_events = active_events[: stop + 1]
        roots = roots[: stop + 1]
        return active_events, roots, True
    return active_events, roots, False


# ---------------------------------------------------------------------------
# solve_ivp driver (reference integrate.py:1303)
# ---------------------------------------------------------------------------
from ._bdf import BDF as _BDFImpl  # noqa: E402
from ._radau import Radau as _RadauImpl  # noqa: E402


class BDF(_BDFImpl, OdeSolver):
    """Stiff variable-order BDF/NDF method (scipy.integrate.BDF; beyond
    the reference's explicit-RK-only menu). See sparse_tpu/_bdf.py."""


class Radau(_RadauImpl, OdeSolver):
    """Stiff L-stable Radau IIA(5) implicit RK (scipy.integrate.Radau;
    beyond the reference). See sparse_tpu/_radau.py."""


METHODS = {"RK23": RK23, "RK45": RK45, "DOP853": DOP853, "BDF": BDF,
           "Radau": Radau}

MESSAGES = {
    0: "The solver successfully reached the end of the integration interval.",
    1: "A termination event occurred.",
}


class OdeResult(dict):
    def __getattr__(self, name):
        try:
            return self[name]
        except KeyError as e:
            raise AttributeError(name) from e

    __setattr__ = dict.__setitem__


def solve_ivp(
    fun,
    t_span,
    y0,
    method="RK45",
    t_eval=None,
    dense_output=False,
    events=None,
    vectorized=False,
    args=None,
    _step_callback=None,
    **options,
):
    """Integrate dy/dt = fun(t, y), scipy-compatible subset (RK methods)."""
    if method not in METHODS and not (
        isinstance(method, type) and issubclass(method, OdeSolver)
    ):
        raise ValueError(f"`method` must be one of {set(METHODS)} or OdeSolver class.")
    t0, tf = map(float, t_span)
    y0 = asjnp(y0)
    if y0.ndim != 1:
        raise ValueError("`y0` must be 1-dimensional.")
    fun = _wrap_fun(fun, args or ())

    if t_eval is not None:
        t_eval = np.asarray(t_eval)
        if t_eval.ndim != 1:
            raise ValueError("`t_eval` must be 1-dimensional.")
        if np.any(t_eval < min(t0, tf)) or np.any(t_eval > max(t0, tf)):
            raise ValueError("Values in `t_eval` are not within `t_span`.")
        d = np.diff(t_eval)
        if tf > t0 and np.any(d <= 0) or tf < t0 and np.any(d >= 0):
            raise ValueError("Values in `t_eval` are not properly sorted.")
        if tf < t0:
            t_eval = t_eval[::-1]

    if isinstance(method, str):
        method = METHODS[method]
    solver = method(fun, t0, y0, tf, vectorized=vectorized, **options)

    if t_eval is None:
        ts = [t0]
        ys = [y0]
    else:
        ts = []
        ys = []
    interpolants = []

    events, is_terminal, event_dir = prepare_events(events, args or ())
    if events is not None:
        g = [float(np.asarray(event(t0, y0))) for event in events]
        t_events = [[] for _ in range(len(events))]
        y_events = [[] for _ in range(len(events))]
    else:
        t_events = None
        y_events = None

    status = None
    while status is None:
        message = solver.step()
        if solver.status == "finished":
            status = 0
        elif solver.status == "failed":
            status = -1
            break
        t_old = solver.t_old
        t = solver.t
        y = solver.y
        if _step_callback is not None:  # checkpoint.py hook
            _step_callback(t, y)

        if dense_output or t_eval is not None or events is not None:
            sol = solver.dense_output()
            if dense_output:
                interpolants.append(sol)
        else:
            sol = None

        if events is not None:
            g_new = [float(np.asarray(event(t, y))) for event in events]
            active = find_active_events(g, g_new, event_dir)
            if active.size > 0:
                root_events, roots, terminate = handle_events(
                    sol, events, active, is_terminal, t_old, t
                )
                for e, te in zip(root_events, roots):
                    t_events[e].append(te)
                    y_events[e].append(sol(te))
                if terminate:
                    status = 1
                    t = roots[-1]
                    y = sol(t)
            g = g_new

        if t_eval is None:
            ts.append(t)
            ys.append(y)
        else:
            if solver.direction > 0:
                t_eval_step = t_eval[
                    (t_eval >= t_old) & (t_eval <= t) & (t_eval > (ts[-1] if ts else -np.inf))
                ]
            else:
                t_eval_step = t_eval[
                    (t_eval <= t_old) & (t_eval >= t) & (t_eval < (ts[-1] if ts else np.inf))
                ]
            if t_eval_step.size > 0:
                for te in t_eval_step:
                    ts.append(float(te))
                    ys.append(sol(np.asarray(float(te))))

    message = MESSAGES.get(status, message)
    if t_events is not None:
        t_events = [np.asarray(te) for te in t_events]
        y_events = [
            (jnp.stack(ye, axis=0) if ye else np.empty((0, solver.n)))
            for ye in y_events
        ]  # [n_occurrences, n], matching scipy

    ts = np.asarray(ts)
    ys_arr = jnp.stack(ys, axis=1) if ys else np.empty((solver.n, 0))

    if dense_output:
        sol_out = OdeSolution(
            np.concatenate([[t0], [i.t for i in interpolants]]), interpolants
        ) if interpolants else None
    else:
        sol_out = None

    return OdeResult(
        t=ts,
        y=ys_arr,
        sol=sol_out,
        t_events=t_events,
        y_events=y_events,
        nfev=solver.nfev,
        njev=solver.njev,
        nlu=solver.nlu,
        status=status,
        message=message,
        success=status >= 0,
    )
