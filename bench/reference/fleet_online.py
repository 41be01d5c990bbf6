"""Plain reference of the paper's slotted fleet under the online policy.

arXiv:2204.13878, Sec. V and VII.B: each user owns a Table II device, apps
arrive per slot as given, and a waiting user schedules its next local
training when Alg. 2's drift-plus-penalty cost of training is no larger
than that of idling (Eqs. 21-23), with the server's lag estimate raised by
every user scheduled before it in the same slot. The queues follow
Eqs. 15-16, the energy Eq. 10, the gap Eq. 4 with the trace-mode momentum
norm v0 / sqrt(1 + 0.05 * version), and every push is applied whole
(replace, weight 1).

Written from the paper and these rules alone: it imports nothing of the
program. ``dtype`` sets the precision of every real-valued quantity;
float64 is the reference and a lower one (bfloat16) the control.
"""
from __future__ import annotations

import numpy as np

APPS = ("Map", "News", "Etrade", "Youtube", "Tiktok", "Zoom", "CandyCru",
        "Angrybird")

# Table II (W, s) and Table III idle power, in catalog order:
# (name, P^b, t_b, P^d, [(P^a, P^a', t_a') per app])
TABLE2 = (
    ("Nexus6", 1.8, 204, 0.238, [
        (3.4, 3.5, 274), (1.7, 2.2, 239), (1.4, 2.4, 236), (0.5, 1.9, 284),
        (1.6, 2.3, 296), (1.2, 2.1, 370), (1.3, 2.3, 997), (2.5, 2.8, 400)]),
    ("Nexus6P", 0.9, 211, 0.486, [
        (0.5, 1.3, 225), (0.44, 1.2, 362), (0.48, 0.96, 228), (0.53, 1.2, 220),
        (1.0, 1.1, 675), (1.4, 1.6, 340), (0.7, 1.3, 280), (1.1, 1.2, 620)]),
    ("Hikey970", 7.87, 213, 0.6, [
        (8.82, 9.42, 186), (9.17, 9.76, 210), (8.50, 9.15, 195),
        (9.15, 11.45, 210), (11.0, 11.2, 271), (7.89, 8.53, 209),
        (11.1, 11.26, 233), (10.1, 10.7, 200)]),
    ("Pixel2", 1.35, 223, 0.689, [
        (1.60, 2.20, 196), (1.82, 2.40, 197), (1.72, 2.23, 206),
        (2.04, 2.21, 226), (2.37, 2.52, 212), (2.57, 3.11, 206),
        (2.89, 2.92, 199), (2.86, 2.88, 285)]),
)

WAIT, TRAIN, COOL = 0, 1, 2


def _sequential(c_sched, c_idle, g_sched, g_idle, H):
    """The waiting users' decisions one by one: user k sees the lag
    estimate raised by the j users scheduled before it. float64 runs on
    Python floats, which round alike and are faster to index."""
    if c_sched.dtype == np.float64:
        c_sched, c_idle, g_sched, g_idle = (x.tolist() for x in
                                            (c_sched, c_idle, g_sched,
                                             g_idle))
        H = float(H)
    go = np.zeros(len(c_idle), bool)
    j = 0
    for k in range(len(c_idle)):
        if c_sched[k] + H * g_sched[j] <= c_idle[k] + H * g_idle[k]:
            go[k] = True
            j += 1
    return go


def simulate(device, app_sched, app_choice, *, V, L_b, epsilon, eta, beta,
             t_d, ready_delay, v_norm0, trace_every, dtype=np.float64):
    """Run the fleet over ``app_sched.shape[0]`` slots.

    ``device[i]`` is user i's row of ``TABLE2``; ``app_sched[t, i]`` says
    an app arrives for user i at slot t (ignored while one runs) and
    ``app_choice[t, i]`` which. Returns the push log columns (t, user,
    lag, gap, corun, weight), the per-user energy and update counts, and
    Q, H and the fleet energy at every ``trace_every``-th slot."""
    f = np.dtype(dtype).type
    T, n = app_sched.shape
    dev = np.asarray(device)
    p_train = np.array([r[1] for r in TABLE2], dtype)[dev]
    t_train = np.array([r[2] for r in TABLE2], dtype)[dev]
    p_idle = np.array([r[3] for r in TABLE2], dtype)[dev]
    p_app = np.array([[a[0] for a in r[4]] for r in TABLE2], dtype)[dev]
    p_corun = np.array([[a[1] for a in r[4]] for r in TABLE2], dtype)[dev]
    t_corun = np.array([[a[2] for a in r[4]] for r in TABLE2], dtype)[dev]
    V, L_b, eps, eta, beta, t_d, v0 = (f(x) for x in
                                       (V, L_b, epsilon, eta, beta, t_d,
                                        v_norm0))
    zero, one = f(0), f(1)
    users = np.arange(n)

    mode = np.full(n, COOL, np.int8)
    cooldown = np.zeros(n, np.int64)
    app = np.full(n, -1, np.int64)
    app_rem = np.zeros(n, dtype)
    train_rem = np.zeros(n, dtype)
    corun = np.zeros(n, bool)
    idle_gap = np.zeros(n, dtype)
    pulled_at = np.zeros(n, np.int64)
    energy = np.zeros(n, dtype)
    updates = np.zeros(n, np.int64)
    version = in_flight = 0
    Q = H = zero
    log = []
    trace_Q, trace_H, trace_E = [], [], []

    def gap(vn, lag):
        """Eq. 4 with the linear-weight-prediction multiplier."""
        return eta * (one - beta ** np.asarray(lag, dtype)) / (one - beta) \
            * vn

    for t in range(T):
        # apps: a running app counts down; a free user may start one
        running = app >= 0
        new = app_sched[t] & ~running
        app_rem[running] -= t_d
        ended = running & (app_rem <= zero)
        app[ended] = -1
        app_rem[ended] = zero
        app[new] = app_choice[t, new]
        app_rem[new] = t_corun[users[new], app[new]]
        has_app = app >= 0
        a = np.maximum(app, 0)
        p_busy = np.where(has_app, p_corun[users, a], p_train)
        p_free = np.where(has_app, p_app[users, a], p_idle)

        # cooldown ends: the user joins the request queue
        cool = mode == COOL
        cooldown[cool] -= 1
        joined = cool & (cooldown <= 0)
        mode[joined] = WAIT
        arrivals = int(np.count_nonzero(joined))

        # Alg. 2 line 6 for each waiting user, in user order
        widx = np.nonzero(mode == WAIT)[0]
        vn = v0 / np.sqrt(one + f(0.05) * f(version))
        g_sched = gap(vn, in_flight + np.arange(len(widx) + 1))
        c_sched = V * p_busy[widx] * t_d - Q
        c_idle = V * p_free[widx] * t_d
        g_idle = idle_gap[widx] + eps
        if H == zero:
            go = c_sched <= c_idle
        else:
            go = _sequential(c_sched, c_idle, g_sched, g_idle, H)
        before = np.cumsum(go) - go
        gaps = np.where(go, g_sched[before], g_idle)
        gap_sum = np.sum(gaps, dtype=dtype)
        start, stay = widx[go], widx[~go]
        idle_gap[stay] += eps
        corun[start] = has_app[start]
        train_rem[start] = np.where(has_app[start],
                                    t_corun[start, a[start]],
                                    t_train[start])
        mode[start] = TRAIN
        pulled_at[start] = version
        in_flight += len(start)
        served = len(start)

        # training: finishers push in user order, each bumping the version
        tr = mode == TRAIN
        train_rem[tr] -= t_d
        fin = np.nonzero(tr & (train_rem <= zero))[0]
        if len(fin):
            vers = version + np.arange(len(fin))
            lags = vers - pulled_at[fin]
            vns = v0 / np.sqrt(one + f(0.05) * vers.astype(dtype))
            log.append((np.full(len(fin), t), fin, lags, gap(vns, lags),
                        corun[fin], np.ones(len(fin), dtype)))
            version += len(fin)
            in_flight -= len(fin)
            updates[fin] += 1
            mode[fin] = COOL
            cooldown[fin] = ready_delay
            idle_gap[fin] = zero

        # Eq. 10 energy, at the app status of the slot
        energy += np.where(mode == TRAIN, p_busy, p_free) * t_d

        # Eqs. 15-16
        Q = max(Q - f(served), zero) + f(arrivals)
        H = max(H + gap_sum - L_b, zero)
        if t % trace_every == 0:
            trace_Q.append(float(Q))
            trace_H.append(float(H))
            trace_E.append(float(np.sum(energy, dtype=np.float64)))

    cols = [np.concatenate([blk[k] for blk in log]) if log else
            np.zeros(0) for k in range(6)]
    return {
        "t": cols[0].astype(np.int64), "user": cols[1].astype(np.int64),
        "lag": cols[2].astype(np.int64), "gap": cols[3].astype(np.float64),
        "corun": cols[4].astype(bool), "weight": cols[5].astype(np.float64),
        "energy": energy.astype(np.float64), "updates": updates,
        "trace_Q": np.array(trace_Q), "trace_H": np.array(trace_H),
        "trace_E": np.array(trace_E),
    }
