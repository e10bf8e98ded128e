"""Plain reference of the simulated cluster: the paper's tick, in NumPy.

It takes nothing from the program.  It reads the configuration file and
the traces, and it steps one member's state, held as a dict of NumPy
arrays, exactly as the paper's simulation defines a tick (arXiv:1807.00368
§3-§5 and the simulator's documented phase order):

1. arrivals join the FIFO queue;
2. progress: a running application advances by ``rate * tick``, where the
   rate is (1 + running elastic) / (1 + elastic) with its whole core set
   running and 0 otherwise; applications that reach their runtime finish
   and leave their slot;
3. the monitor samples each running component's usage (its utilization
   profile, linear between knots, stepped for "jumpy" applications, times
   its reservation) into a ring of the last ``window`` samples;
4. shaping (not under ``baseline``): each component with ``grace`` samples
   gets a GP forecast of its peak over ``horizon`` ticks, the safeguard
   ``clip(peak + k1 * request + k2 * sigma, 0, request)``, and Algorithm 1
   (pessimistic) re-fits the applications in FIFO order: core components
   fit per host or the whole application is preempted and requeued,
   elastic components fit oldest first or are preempted alone;
5. OOM: on every host whose memory usage exceeds its capacity, the
   component with the largest usage over allocation is killed until the
   host fits; a core victim fails its application, which is requeued;
6. admission: FIFO heads are placed worst-fit (most free memory first),
   core components first, while the head fits and a slot is free; then
   missing elastic components are re-placed where they fit;
7. the tick's totals: running applications, usage and allocation summed
   over the cluster, and the forecast rows (ready, and computed by a
   forecast bucket of the size picked at the chunk's start).

The bookkeeping is in float32, as the configuration states, with sums in
a fixed pairwise order.  The GP forecast is computed in float64 (``FINE``).
The control (``CONTROL``) is the same reference one precision down: every
GP operation, each application's progress and each usage sample rounded
to bfloat16.  The GP follows §3.1.2: pattern inputs
``[t, y_{t-h} .. y_{t-1}]`` over the standardised window, the exponential
kernel on their distance, evidence maximisation by a fixed number of Adam
steps on log(ell, sigma_f, sigma_n), and an iterated forecast that feeds
each predicted mean back into the history.
"""
from __future__ import annotations

import numpy as np

CPU, MEM = 0, 1
SEGMENTS = 32
F32 = np.float32

FINE = "float64"
CONTROL = "bfloat16"


def _round_bf16(x):
    """Round to the nearest bfloat16 (ties to even), kept as float32."""
    x = np.asarray(x, np.float32)
    b = x.view(np.uint32)
    b = (b + np.uint32(0x7FFF) + ((b >> np.uint32(16)) & np.uint32(1))) & np.uint32(0xFFFF0000)
    out = b.view(np.float32)
    return np.where(np.isfinite(x), out, x)


def tree_sum(x: np.ndarray, axis: int = -1) -> np.ndarray:
    """Pairwise sum over ``axis``: halves added until one is left, an odd
    length first padded with a zero (one fixed order of float adds)."""
    x = np.moveaxis(np.asarray(x), axis, 0)
    while x.shape[0] > 1:
        if x.shape[0] % 2:
            x = np.concatenate([x, np.zeros_like(x[:1])])
        half = x.shape[0] // 2
        x = x[:half] + x[half:]
    return x[0]


# ----------------------------------------------------------------------
# GP forecast (§3.1.2), batched over rows
# ----------------------------------------------------------------------

class GP:
    """The GP forecaster of the configuration, over (R, W) windows."""

    def __init__(self, gp: dict, horizon: int, precision: str = FINE):
        if gp["kernel"] != "exp":
            raise ValueError("the reference knows the exponential kernel only")
        self.h = int(gp["history"])
        self.n = int(gp["max_patterns"])
        self.steps = int(gp["opt_steps"])
        self.lr = float(gp["opt_lr"])
        self.jitter = float(gp["jitter"])
        self.horizon = int(horizon)
        if precision == FINE:
            self.dt, self.q = np.float64, (lambda x: x)
        elif precision == CONTROL:
            self.dt, self.q = np.float32, _round_bf16
        else:
            raise ValueError(f"unknown precision {precision!r}")

    # every arithmetic result passes through q (identity, or a bf16 round)
    def _chol(self, a):
        q = self.q
        n = a.shape[-1]
        rows = np.arange(n)
        cols = []
        for j in range(n):
            d = q(np.sqrt(a[:, j, j]))
            c = np.where(rows[None] > j, q(a[:, :, j] / d[:, None]),
                         np.where(rows[None] == j, d[:, None], 0.0))
            cols.append(c)
            a = q(a - q(c[:, :, None] * c[:, None, :]))
        return np.stack(cols, axis=2)

    def _solve_lower(self, L, b):
        q = self.q
        n = L.shape[-1]
        z = []
        for k in range(n):
            zk = q(b[:, k] / (L[:, k, k] if b.ndim == 2 else L[:, k, k][:, None]))
            z.append(zk)
            col = L[:, :, k] if b.ndim == 2 else L[:, :, k][:, :, None]
            b = q(b - q(col * (zk[:, None] if b.ndim == 2 else zk[:, None, :])))
        return np.stack(z, axis=1)

    def _cho_solve(self, L, b):
        q = self.q
        n = L.shape[-1]
        z = self._solve_lower(L, b)
        x = [None] * n
        for k in reversed(range(n)):
            d = L[:, k, k] if z.ndim == 2 else L[:, k, k][:, None]
            x[k] = q(z[:, k] / d)
            row = L[:, k, :] if z.ndim == 2 else L[:, k, :][:, :, None]
            xk = x[k][:, None] if z.ndim == 2 else x[k][:, None, :]
            z = q(z - q(row * xk))
        return np.stack(x, axis=1)

    def _inverse(self, a, y):
        """(a^-1, a^-1 y, Cholesky factor or None) of SPD matrices ``a``:
        by LAPACK in float64, by the written-out Cholesky in the control's
        rounding (which the factor is then needed for)."""
        if self.dt == np.float64:
            kinv = np.linalg.inv(a)
            return kinv, np.einsum("rij,rj->ri", kinv, y), None
        L = self._chol(a)
        n = a.shape[-1]
        kinv = self._cho_solve(L, np.broadcast_to(np.eye(n, dtype=a.dtype), a.shape).copy())
        return kinv, self._cho_solve(L, y), L

    def _kernel(self, r, ell, sf):
        q = self.q
        return q(q(sf * sf)[:, None, None] * q(np.exp(q(-r / ell[:, None, None]))))

    def forecast(self, windows: np.ndarray, valid: np.ndarray):
        """(peak mean, its variance) over the horizon, per row."""
        q, dt = self.q, self.dt
        R, T = windows.shape
        h, n = self.h, min(self.n, windows.shape[1] - self.h)
        w = windows.astype(dt)
        v = valid.astype(dt)
        cnt = np.maximum(v.sum(1), 1.0)
        mu = q(q((w * v).sum(1)) / cnt)
        var = q(q((q(w - mu[:, None]) ** 2 * v).sum(1)) / cnt)
        sd = q(np.sqrt(np.maximum(var, 1e-10)))
        z = q(q(w - mu[:, None]) / sd[:, None])

        tgt = np.arange(T - n, T)
        t_feat = (tgt / max(T - 1, 1)).astype(dt)
        X = np.concatenate(
            [np.broadcast_to(t_feat, (R, n))[:, :, None],
             z[:, tgt[:, None] + np.arange(-h, 0)[None, :]]], axis=2)
        y = z[:, tgt]
        rv = valid[:, tgt[:, None] + np.arange(-h, 1)[None, :]].all(2)

        def dist(a, b):
            d2 = q(((a[:, :, None, :] - b[:, None, :, :]) ** 2).sum(-1))
            return q(np.sqrt(q(d2 + 1e-12)))

        r = dist(X, X)
        p = np.log(np.array([1.0, 1.0, 0.3])).astype(dt)
        p = np.broadcast_to(p, (R, 3)).copy()
        m = np.zeros_like(p)
        s = np.zeros_like(p)
        b1, b2, eps = 0.9, 0.999, 1e-8
        eye = np.eye(n, dtype=dt)
        for i in range(self.steps):
            ell, sf, sn = (q(np.exp(p[:, k])) for k in range(3))
            K = self._kernel(r, ell, sf)
            noise = np.where(rv, q(q(sn * sn) + self.jitter)[:, None], 1e6)
            kinv, alpha, _ = self._inverse(K + eye * noise[:, :, None], y)
            dK = q(0.5 * q(kinv - q(alpha[:, :, None] * alpha[:, None, :])))
            g_ell = q((q(dK * q(K * q(r / ell[:, None, None])))).sum((1, 2)))
            g_sf = q((q(dK * q(2.0 * K))).sum((1, 2)))
            ds2 = q(np.where(rv, np.diagonal(dK, axis1=1, axis2=2), 0.0).sum(1))
            g_sn = q(ds2 * q(2.0 * q(sn * sn)))
            g = np.stack([g_ell, g_sf, g_sn], 1)
            g = np.where(np.isfinite(g), g, 0.0)
            m = q(b1 * m + q((1 - b1) * g))
            s = q(b2 * s + q((1 - b2) * q(g * g)))
            mh = q(m / (1 - b1 ** (i + 1.0)))
            vh = q(s / (1 - b2 ** (i + 1.0)))
            p = np.clip(q(p - q(self.lr * q(mh / q(np.sqrt(vh) + eps)))), -6.0, 6.0)

        ell, sf, sn = (q(np.exp(p[:, k])) for k in range(3))
        K = self._kernel(r, ell, sf)
        noise = np.where(rv, q(q(sn * sn) + self.jitter)[:, None], 1e6)
        kinv, alpha, L = self._inverse(K + eye * noise[:, :, None], y)
        hist = z[:, T - h:]
        means, variances = [], []
        for k in range(self.horizon):
            t_next = (T + k) / max(T - 1, 1)
            xs = np.concatenate([np.full((R, 1), t_next, dt), hist], 1)
            ks = self._kernel(dist(xs[:, None, :], X), ell, sf)[:, 0, :]
            mean_k = q((q(ks * alpha)).sum(1))
            if L is None:       # ks^T K^-1 ks
                quad = np.einsum("ri,rij,rj->r", ks, kinv, ks)
            else:               # |L^-1 ks|^2
                vv = self._solve_lower(L, ks)
                quad = q((vv * vv).sum(1))
            var_k = np.maximum(q(q(q(sf * sf) + q(sn * sn)) - quad), 1e-9)
            means.append(mean_k)
            variances.append(var_k)
            hist = np.concatenate([hist[:, 1:], mean_k[:, None]], 1)
        mean = q(q(np.stack(means, 1) * sd[:, None]) + mu[:, None])
        var = q(np.stack(variances, 1) * q(sd * sd)[:, None])
        enough = valid.sum(1) >= h + 1
        last = w[:, -1]
        mean = np.where(enough[:, None], mean, last[:, None])
        var = np.where(enough[:, None], var,
                       q(q(0.5 * np.abs(last) + 1.0) ** 2)[:, None])
        k = np.argmax(mean, axis=1)
        pick = np.arange(R)
        return mean[pick, k], var[pick, k]


def shaped(peak, req, var, k1: float, k2: float):
    """The safeguard (paper Eq. 9): clip(peak + k1 * R + k2 * sigma, 0, R)."""
    sigma = np.sqrt(np.maximum(var, 0.0))
    return np.clip(peak + k1 * req + k2 * sigma, 0.0, req)


def windows_of(mon_buf: np.ndarray, mon_count: np.ndarray, rows: np.ndarray):
    """(2R, W) CPU-then-MEM windows and validity of monitor ``rows``."""
    W = mon_buf.shape[1]
    wins = np.concatenate([mon_buf[rows, :, CPU], mon_buf[rows, :, MEM]])
    cnt = np.minimum(mon_count[rows], W)
    vrow = np.arange(W)[None, :] >= (W - cnt)[:, None]
    return wins, np.concatenate([vrow, vrow])


# ----------------------------------------------------------------------
# the tick
# ----------------------------------------------------------------------

class Cluster:
    """One configuration's cluster and tick, stepping member states."""

    def __init__(self, spec: dict, precision: str = FINE):
        sim = spec["sim"]
        for key in ("calibration", "control", "obs", "leap"):
            if sim.get(key):
                raise ValueError(f"the reference has no {key} path")
        if sim["policy"] not in ("pessimistic", "baseline"):
            raise ValueError(f"the reference has no {sim['policy']} policy")
        if not sim["work_lost_on_kill"]:
            raise ValueError("the reference kills lose their work only")
        cl = sim["cluster"]
        self.H, self.A = int(cl["n_hosts"]), int(cl["max_running_apps"])
        self.cap = np.stack([np.full(self.H, cl["host_cpu"], F32),
                             np.full(self.H, cl["host_mem"], F32)], -1)
        self.tick = F32(cl["tick"])
        self.policy = sim["policy"]
        self.grace = int(sim["grace"])
        self.k1 = float(sim["safeguard"]["k1"])
        self.k2 = float(sim["safeguard"]["k2"])
        self.gp = (GP(sim["gp"], sim["horizon"], precision)
                   if self.policy != "baseline" else None)
        if self.gp is not None and sim["forecaster"] != "gp":
            raise ValueError("the reference forecasts with the GP only")
        self.bucketed = bool(sim["forecast_bucket"]) and self.gp is not None
        # hosts whose free memory lies within this of the most free count
        # as tied in worst-fit placement where allocations are shaped: the
        # program's float32 forecasts leave each host's free memory off the
        # reference's (sound runs on the chip: at most 0.048 GB, 11 seeds of
        # paper-gp.google-c8), and two hosts may be off in opposite ways;
        # this is five times twice that.  Among tied hosts the reference
        # takes the one the program's state shows, where it shows one; a
        # placement further from the worst fit is the reference's own and
        # is counted.  Unshaped (baseline) allocations agree exactly.
        # Where the program's state shows no host, as for an application
        # that leaves within the chunk, the tie is blind: the reference
        # takes the most free and notes it (``blind``); ``flips`` names
        # the blind ties, by their order, at which it takes the runner-up
        # instead (``check.replay`` searches them).
        self.tie_mem = F32(0.5) if self.gp is not None else F32(0.0)
        self.ties = []      # free memory below the most free of each tie taken
        self.blind = []     # the same, of each blind tie met
        self.flips = frozenset()
        # the control rounds the bookkeeping's float32 values to bfloat16 too
        self.qb = _round_bf16 if precision == CONTROL else (lambda x: x)

    # -- chunk-level: the forecast bucket the driver picks ---------------

    def bucket(self, states: list, min_bucket: int = 8):
        """Smallest power of two >= 8 covering the most ready rows of any
        member; None where that covers the whole table."""
        n = max(int(self._ready(st).sum()) for st in states)
        AC = states[0]["mon_count"].shape[0]
        b = min_bucket
        while b < n:
            b *= 2
        return None if b >= AC else b

    def _ready(self, st):
        run = (st["slot_gid"] >= 0)[:, None] & st["comp_running"]
        return run.reshape(-1) & (st["mon_count"] >= self.grace)

    # -- one tick over a cohort -------------------------------------------

    def chunk(self, traces: list, states: list, ticks: int, hints: list | None = None):
        """Step every member ``ticks`` ticks; returns the new states and,
        per member, the per-tick totals.  ``hints``, the program's states
        after the chunk, break worst-fit ties (``tie_mem``)."""
        bucket = self.bucket(states) if self.bucketed else None
        states = [{k: np.array(v, copy=True) for k, v in st.items()} for st in states]
        hints = hints or [None] * len(states)
        rows = [[] for _ in states]
        for _ in range(ticks):
            pre = [self._front(tr, st) for tr, st in zip(traces, states)]
            fc = self._forecast(states, pre)
            for i, (tr, st) in enumerate(zip(traces, states)):
                rows[i].append(self._back(tr, st, pre[i], fc[i], bucket, hints[i]))
        return states, rows

    def _forecast(self, states, pre):
        """GP (peak, var) of each member's ready rows, in one batch."""
        out = [None] * len(states)
        if self.gp is None:
            return out
        wins, valids, spans = [], [], []
        for st, p in zip(states, pre):
            if p is None:
                spans.append(0)
                continue
            rows = np.nonzero(self._ready(st))[0]
            w, v = windows_of(st["mon_buf"], st["mon_count"], rows)
            wins.append(w)
            valids.append(v)
            spans.append(len(rows))
        if sum(spans):
            peak, var = self.gp.forecast(np.concatenate(wins), np.concatenate(valids))
        at = 0
        for i, n in enumerate(spans):
            out[i] = (peak[at:at + 2 * n], var[at:at + 2 * n]) if n else None
            at += 2 * n
        return out

    def _front(self, tr, st):
        """Arrivals, progress and completions, monitor sampling.  Returns
        the tick's working values, or None once every app is done."""
        if st["done"].all():
            return None
        A = self.A
        C = st["comp_running"].shape[1]
        t = F32(st["t"] + self.tick)
        new = ~st["arrived"] & (tr["submit"] <= t)
        st["arrived"] |= new
        st["queued"] |= new

        run = st["slot_gid"] >= 0
        gid = np.maximum(st["slot_gid"], 0)
        is_core = tr["is_core"][gid]
        exists = tr["exists"][gid]
        core_ok = ((is_core & st["comp_running"]).sum(1) == is_core.sum(1))
        n_el = (exists & ~is_core).sum(1)
        n_run_el = (st["comp_running"] & ~is_core).sum(1)
        rate = (core_ok.astype(F32) * F32(1.0 + n_run_el)) / F32(1.0 + n_el)
        rate = np.where(run, rate, F32(0.0)).astype(F32)
        work = self.qb((st["work_done"] + rate * self.tick).astype(F32))
        st["work_done"] = work
        fin = run & (work >= tr["runtime"][gid])
        for slot in np.nonzero(fin)[0]:
            g = gid[slot]
            st["done"][g] = True
            st["finish_t"][g] = max(st["finish_t"][g], t)
        self._evict(st, fin)
        resets = np.repeat(fin, C)

        gid = np.maximum(st["slot_gid"], 0)
        prog = np.clip(st["work_done"] / tr["runtime"][gid], F32(0), F32(1)).astype(F32)
        usage = self._usage(tr, st, prog)
        live = ((st["slot_gid"] >= 0)[:, None] & st["comp_running"]).reshape(-1)
        buf = st["mon_buf"]
        new_row = usage.reshape(A * C, 2)
        buf[live] = np.concatenate([buf[live, 1:], new_row[live][:, None, :]], 1)
        st["mon_count"] = st["mon_count"] + live
        return {"t": t, "usage": usage, "resets": resets}

    def _usage(self, tr, st, prog):
        C = st["comp_running"].shape[1]
        gid = np.maximum(st["slot_gid"], 0)
        x = (prog * F32(SEGMENTS - 1)).astype(F32)
        s0 = np.minimum(x.astype(np.int32), SEGMENTS - 2)
        frac = (x - s0).astype(F32)
        comps = np.arange(C)[None, :]
        lv0 = tr["levels"][gid[:, None], comps, s0[:, None]]
        lv1 = tr["levels"][gid[:, None], comps, s0[:, None] + 1]
        out = lv0 + (lv1 - lv0) * frac[:, None, None]
        out = np.where(tr["is_jumpy"][gid][:, None, None], lv0, out)
        req = np.stack([tr["cpu_req"][gid], tr["mem_req"][gid]], -1)
        run = (st["slot_gid"] >= 0)[:, None] & st["comp_running"]
        return self.qb((out * req * run[:, :, None]).astype(F32))

    def _evict(self, st, mask):
        st["slot_gid"] = np.where(mask, -1, st["slot_gid"]).astype(np.int32)
        st["comp_running"] = st["comp_running"] & ~mask[:, None]
        st["alloc"] = np.where(mask[:, None, None], F32(0), st["alloc"]).astype(F32)
        st["work_done"] = np.where(mask, F32(0), st["work_done"]).astype(F32)

    def _back(self, tr, st, pre, fc, bucket, hint=None):
        """Shaping, OOM, admission and the tick's totals."""
        if pre is None:
            return {"valid": False, "n_running": 0, "used_cpu": 0.0,
                    "used_mem": 0.0, "alloc_cpu": 0.0, "alloc_mem": 0.0,
                    "forecast_rows": 0, "forecast_rows_done": 0}
        A, C = st["comp_running"].shape
        t, usage, resets = pre["t"], pre["usage"], pre["resets"]
        fc_rows = fc_done = 0
        if self.policy != "baseline":
            ready = self._ready(st)
            n_ready = int(ready.sum())
            fc_rows = 2 * n_ready
            if bucket is None:
                fc_done = 2 * A * C if n_ready else 0
            else:
                fc_done = -(-n_ready // bucket) * 2 * bucket
            usage, r4 = self._shape(tr, st, ready, fc, usage, t)
            resets = resets | r4
        usage, r5 = self._oom(tr, st, usage)
        r6 = self._admit(tr, st, t, hint)
        self._place_missing(tr, st, t, hint)
        mask = resets | r5 | r6
        st["mon_buf"][mask] = 0.0
        st["mon_count"] = np.where(mask, 0, st["mon_count"]).astype(np.int32)

        used = tree_sum(tree_sum(usage, 1), 0)
        alloc = tree_sum(tree_sum(np.where(st["comp_running"][:, :, None],
                                           st["alloc"], F32(0)), 1), 0)
        st["t"] = F32(t)
        return {"valid": True, "n_running": int((st["slot_gid"] >= 0).sum()),
                "used_cpu": float(used[CPU]), "used_mem": float(used[MEM]),
                "alloc_cpu": float(alloc[CPU]), "alloc_mem": float(alloc[MEM]),
                "forecast_rows": fc_rows, "forecast_rows_done": fc_done}

    # -- phase 4: forecast, safeguard, Algorithm 1 --------------------------

    def _shape(self, tr, st, ready, fc, usage, t):
        A, C = st["comp_running"].shape
        H = self.H
        gid = np.maximum(st["slot_gid"], 0)
        exists_app = st["slot_gid"] >= 0
        run = exists_app[:, None] & st["comp_running"]
        req = np.stack([tr["cpu_req"][gid], tr["mem_req"][gid]], -1)
        demand = np.where(run[:, :, None], req, F32(0)).astype(np.float64)
        if fc is not None:
            rows = np.nonzero(ready)[0]
            n = len(rows)
            peak, var = fc
            for r, off in ((CPU, 0), (MEM, n)):
                rq = req.reshape(A * C, 2)[rows, r].astype(np.float64)
                sh = shaped(peak[off:off + n], rq, var[off:off + n], self.k1, self.k2)
                demand.reshape(A * C, 2)[rows, r] = sh

        # Algorithm 1, apps in FIFO order of their first submission
        key = np.where(exists_app, tr["submit"][gid], np.inf)
        order = np.argsort(key, kind="stable")[:int(exists_app.sum())]
        core_tbl = tr["is_core"][gid] & exists_app[:, None]
        free = self.cap.astype(np.float64).copy()
        kill_app = np.zeros(A, bool)
        kill_comp = np.zeros((A, C), bool)
        comp_alive = (t - st["alive_since"]).astype(F32)
        for a in order:
            live = st["comp_running"][a]
            core = live & core_tbl[a]
            core_dem = np.zeros((H, 2))
            for c in np.nonzero(core)[0]:
                core_dem[st["comp_host"][a, c]] += demand[a, c]
            trial = free - core_dem
            if (trial < 0).any():
                kill_app[a] = True
                continue
            free = trial
            el = np.nonzero(live & ~core_tbl[a])[0]
            el = el[np.argsort(-comp_alive[a, el], kind="stable")]
            for c in el:
                h = st["comp_host"][a, c]
                if (free[h] - demand[a, c] <= 0).any():
                    kill_comp[a, c] = True
                else:
                    free[h] -= demand[a, c]

        survive = st["comp_running"] & exists_app[:, None] & ~kill_app[:, None] & ~kill_comp
        alloc = np.where(survive[:, :, None], demand, 0.0)

        kills = kill_app & exists_app
        for slot in np.nonzero(kills)[0]:
            st["queued"][gid[slot]] = True
        st["full_preemptions"] = np.int32(st["full_preemptions"] + kills.sum())
        usage = np.where(kills[:, None, None], F32(0), usage)
        self._evict(st, kills)
        kc = kill_comp & exists_app[:, None] & st["comp_running"]
        usage = np.where(kc[:, :, None], F32(0), usage)
        st["comp_running"] = st["comp_running"] & ~kc
        st["partial_preemptions"] = np.int32(st["partial_preemptions"] + kc.sum())
        st["alloc"] = np.where(st["comp_running"][:, :, None], alloc, 0.0).astype(F32)
        return usage, np.repeat(kills, C) | kc.reshape(-1)

    # -- phase 5: the OS's OOM killer ----------------------------------------

    def _oom(self, tr, st, usage):
        A, C = st["comp_running"].shape
        cap = self.cap[:, MEM]
        resets = np.zeros(A * C, bool)
        mem0 = np.where(st["comp_running"], usage[:, :, MEM], F32(0))
        over = [h for h in range(self.H)
                if tree_sum(tree_sum(np.where(st["comp_host"] == h, mem0, F32(0)), 1), 0)
                > cap[h] + F32(1e-6)]
        for h in over:
            while True:
                on_h = st["comp_running"] & (st["comp_host"] == h)
                mem = usage[:, :, MEM]
                tot = tree_sum(tree_sum(np.where(on_h, mem, F32(0)), 1), 0)
                if not on_h.any() or not tot > cap[h] + F32(1e-6):
                    break
                over_by = np.where(on_h, mem - st["alloc"][:, :, MEM], -np.inf).reshape(-1)
                vic = int(np.nonzero(over_by == over_by.max())[0][-1])
                a, c = divmod(vic, C)
                g = int(st["slot_gid"][a])
                if tr["is_core"][g, c]:
                    usage[a] = 0.0
                    self._evict(st, np.arange(A) == a)
                    st["failed"][g] = True
                    st["queued"][g] = True
                    st["oom_kills"] = np.int32(st["oom_kills"] + 1)
                    st["failure_events"] = np.int32(st["failure_events"] + 1)
                else:
                    usage[a, c] = 0.0
                    st["comp_running"][a, c] = False
                    st["alloc"][a, c] = 0.0
                    resets[vic] = True
                    st["partial_preemptions"] = np.int32(st["partial_preemptions"] + 1)
        return usage, resets

    # -- phase 6: FIFO admission, worst fit --------------------------------

    def _free(self, st):
        A, C = st["comp_running"].shape
        live = st["comp_running"].reshape(-1)
        host = st["comp_host"].reshape(-1)
        mask = live[:, None] & (host[:, None] == np.arange(self.H)[None, :])
        used = tree_sum(np.where(mask[:, :, None], st["alloc"].reshape(-1, 2)[:, None, :],
                                 F32(0)), 0)
        return (self.cap - used).astype(F32)

    def _worst_fit(self, free, cpu, mem, want: int = -1):
        """The fitting host with the most free memory (the first of equals);
        ``want``, where it fits and is tied with it (``tie_mem``).  With no
        ``want``, a fitting host tied with the most free makes a blind tie,
        where ``flips`` may take the most free of the others."""
        ok = (free[:, CPU] >= cpu) & (free[:, MEM] >= mem)
        if not ok.any():
            return -1
        best = int(np.argmax(np.where(ok, free[:, MEM], -np.inf)))
        if want >= 0:
            if ok[want] and free[want, MEM] >= free[best, MEM] - self.tie_mem:
                if want != best:
                    self.ties.append(float(free[best, MEM] - free[want, MEM]))
                return want
            return best
        near = ok & (free[:, MEM] >= free[best, MEM] - self.tie_mem)
        near[best] = False
        if self.tie_mem > 0 and near.any():
            rival = int(np.argmax(np.where(near, free[:, MEM], -np.inf)))
            self.blind.append(float(free[best, MEM] - free[rival, MEM]))
            if len(self.blind) - 1 in self.flips:
                return rival
        return best

    @staticmethod
    def _shown(hint, slot: int, gid: int, c: int) -> int:
        """The host the program's state shows for (slot, c), or -1."""
        if hint is None or hint["slot_gid"][slot] != gid or not hint["comp_running"][slot, c]:
            return -1
        return int(hint["comp_host"][slot, c])

    def _admit(self, tr, st, t, hint=None):
        A, C = st["comp_running"].shape
        resets = np.zeros(A * C, bool)
        while st["queued"].any() and (st["slot_gid"] < 0).any():
            q = np.nonzero(st["queued"])[0]
            head = int(q[np.argmin(tr["submit"][q])])   # ties: lowest id
            slot = int(np.argmax(st["slot_gid"] < 0))
            free = self._free(st)
            cpu, mem = tr["cpu_req"][head], tr["mem_req"][head]
            needed = tr["exists"][head]
            core = needed & tr["is_core"][head]
            place = np.full(C, -1)
            ok = True
            for c in range(C):
                if not core[c]:
                    continue
                h = self._worst_fit(free, cpu[c], mem[c], self._shown(hint, slot, head, c))
                if h < 0 or not ok:
                    ok = False
                    continue
                free[h] -= (cpu[c], mem[c])
                place[c] = h
            if not ok:
                break
            for c in range(C):
                if needed[c] and not core[c]:
                    h = self._worst_fit(free, cpu[c], mem[c], self._shown(hint, slot, head, c))
                    if h >= 0:
                        free[h] -= (cpu[c], mem[c])
                        place[c] = h
            placed = place >= 0
            st["slot_gid"][slot] = head
            st["work_done"][slot] = 0.0
            st["comp_running"][slot] = placed
            st["comp_host"][slot] = np.maximum(place, 0)
            st["alloc"][slot] = np.where(placed[:, None], np.stack([cpu, mem], -1), F32(0))
            st["alive_since"][slot] = t
            st["queued"][head] = False
            resets[slot * C:(slot + 1) * C] = True
        return resets

    def _place_missing(self, tr, st, t, hint=None):
        A, C = st["comp_running"].shape
        gid = np.maximum(st["slot_gid"], 0)
        missing = ((st["slot_gid"] >= 0)[:, None] & tr["exists"][gid]
                   & ~tr["is_core"][gid] & ~st["comp_running"]).reshape(-1)
        if not missing.any():
            return
        free = self._free(st)
        for i in np.nonzero(missing)[0]:
            a, c = divmod(int(i), C)
            cpu, mem = tr["cpu_req"][gid[a], c], tr["mem_req"][gid[a], c]
            h = self._worst_fit(free, cpu, mem, self._shown(hint, a, int(gid[a]), c))
            if h < 0:
                continue
            free[h] -= (cpu, mem)
            st["comp_running"][a, c] = True
            st["comp_host"][a, c] = h
            st["alloc"][a, c] = (cpu, mem)
            st["alive_since"][a, c] = t


def trace_arrays(trace) -> dict:
    """The columns of one member's trace that a tick reads."""
    return {"submit": np.asarray(trace.submit, F32),
            "runtime": np.asarray(trace.runtime, F32),
            "cpu_req": np.asarray(trace.cpu_req, F32),
            "mem_req": np.asarray(trace.mem_req, F32),
            "is_core": np.asarray(trace.is_core, bool),
            "is_jumpy": np.asarray(trace.is_jumpy, bool),
            "levels": np.asarray(trace.levels, F32),
            "exists": np.asarray(trace.cpu_req, F32) > 0}


__all__ = ["CONTROL", "FINE", "GP", "Cluster", "shaped", "trace_arrays",
           "tree_sum", "windows_of"]
