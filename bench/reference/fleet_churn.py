"""Plain reference of the paper's slotted fleet under the online policy, with
device churn: Markov availability, battery gating and a good/bad network.

The fleet, Alg. 2, the queues, the energy and the push log are those of
``fleet_online`` (arXiv:2204.13878, Sec. V and VII.B), whose tables this
module imports. Churn follows the rules the simulator documents for its
Markov dynamics, applied at the top of every slot before anything else:

- a user's availability chain turns off with probability ``p_off`` (per
  device class) and back on with ``p_on``; its network chain turns bad
  with ``p_net_bad`` and good again with ``p_net_recover``;
- its battery drains by ``drain_corun`` or ``drain_train`` a second
  while it trained, and was up, at the start of the slot, and charges by
  ``charge_rate`` a second otherwise, clipped to ``[0, capacity]``. The
  level is a whole count of ``battery_quantum`` (capacity units a
  quantum; starting levels round to the nearest), or a real number of
  capacity units without one;
- the battery holds a user down from the slot its level is at or below
  ``battery_min`` until the slot it is above ``battery_resume``;
- a user is up while its chain is on and its battery does not hold it
  down. A user that goes down leaves the request queue if it
  waited, loses its training if it trained (no push; the in-flight count
  drops), and parks; a user that comes back up while parked cools down
  for ``ready_delay`` slots plus ``net_delay_slots`` while its network is
  bad, then joins the queue as a normal re-arrival.

Three rules the paper does not give, taken from the simulator's design:
departures extend Eq. 15 (``Q <- max(Q - served - departures, 0) +
arrivals``); a device that is down draws no power; and the bad-network
delay is added at every re-arrival, after a push as after a recovery.

Departure from the plain-NumPy rule: the per-slot uniforms are drawn with
``jax.random`` on the host's CPU, used as a library for the input stream
only. The run key is ``[0, seed]`` (uint32); every slot splits it into the
next key and a subkey and draws ``uniform(subkey, (2, n), float32)``: row 0
drives the availability chains, row 1 the network chains. Nothing of the
program is imported. ``dtype`` sets the precision of every real-valued
quantity; float64 is the reference and a lower one (bfloat16) the control.
"""
from __future__ import annotations

import numpy as np

from bench.reference.fleet_online import COOL, TABLE2, TRAIN, WAIT, \
    _sequential

OFF = 3


def slot_uniforms(seed: int, n: int, T: int):
    """The ``(2, n)`` float32 uniforms of each of ``T`` slots, in order."""
    import jax
    import jax.numpy as jnp

    def step(key):
        key, sub = jax.random.split(key)
        return key, jax.random.uniform(sub, (2, n), jnp.float32)

    cpu = jax.devices("cpu")[0]
    with jax.default_device(cpu):
        step = jax.jit(step)
        key = jnp.asarray(np.array([0, seed & 0xFFFFFFFF], np.uint32))
        for _ in range(T):
            key, u = step(key)
            yield np.asarray(u)


def simulate(device, app_sched, app_choice, battery0, *, seed, V, L_b,
             epsilon, eta, beta, t_d, ready_delay, v_norm0, trace_every,
             p_off, p_on, battery_capacity, drain_train, drain_corun,
             charge_rate, battery_min, p_net_bad,
             p_net_recover, net_delay_slots, battery_resume=None,
             battery_quantum=None, dropout="lose", dtype=np.float64):
    """Run the churning fleet over ``app_sched.shape[0]`` slots.

    As ``fleet_online.simulate``, plus ``battery0[i]``, user i's starting
    charge as a capacity fraction, and the churn knobs (``p_off`` and
    ``p_on`` a scalar or one value per ``TABLE2`` row). Returns its
    answers, and the churn: each user's final ``battery`` (in quanta
    with ``battery_quantum``) and ``drops``
    (trainings lost), the run totals ``departures``, ``recoveries``,
    ``lost`` and ``started`` (trainings begun), and the largest shares of
    users held down by the battery (``battery_down_peak``, at one slot;
    ``battery_down_ever``, at any) and on a bad network
    (``net_bad_peak``), and how often the battery gate turned a user down
    (``battery_falls``) and let one up again (``battery_rises``) after
    the first slot."""
    if dropout != "lose":
        raise ValueError(f"the reference knows the 'lose' rule only, "
                         f"got {dropout!r}")
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

    def per_class(p):
        p = np.asarray(p, np.float64)
        return (np.full(n, p) if p.ndim == 0 else p[dev]).astype(dtype)

    q_off, q_on = per_class(p_off), per_class(p_on)
    q_bad, q_good = f(p_net_bad), f(p_net_recover)
    unit = 1.0 if battery_quantum is None else float(battery_quantum)
    b_resume = battery_min if battery_resume is None else battery_resume
    cap, b_min, b_resume = (f(round(x / unit) if battery_quantum else x)
                            for x in (battery_capacity, battery_min,
                                      b_resume))
    # the battery's per-slot steps: drain in training, co-running; charge
    d_train, d_corun, charge = (
        f(round(r / unit) if battery_quantum else r) * t_d
        for r in (drain_train, drain_corun, charge_rate))
    level = np.asarray(battery0, np.float64) * battery_capacity
    battery = (np.rint(level / unit) if battery_quantum else level
               ).astype(dtype)
    low = np.zeros(n, bool)
    on = np.ones(n, bool)
    up = np.ones(n, bool)
    net_bad = np.zeros(n, bool)
    drops = np.zeros(n, np.int64)
    departures = recoveries = lost = started = falls = rises = 0
    down_peak = net_peak = 0
    down_ever = np.zeros(n, bool)

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

    for t, u in enumerate(slot_uniforms(seed, n, T)):
        # churn: battery, chains, effective availability, then effects
        burning = (mode == TRAIN) & up
        step = np.where(burning, -np.where(corun, d_corun, d_train), charge)
        battery = np.clip(battery + step, zero, cap)
        on = np.where(on, u[0] >= q_off, u[0] < q_on)
        net_bad = np.where(net_bad, u[1] >= q_good, u[1] < q_bad)
        was_low = low
        low = np.where(low, battery <= b_resume, battery <= b_min)
        if t:
            falls += int(np.count_nonzero(low & ~was_low))
            rises += int(np.count_nonzero(was_low & ~low))
        was_up, up = up, on & ~low
        down_ever |= low
        down_peak = max(down_peak, int(np.count_nonzero(low)))
        net_peak = max(net_peak, int(np.count_nonzero(net_bad)))
        net_extra = np.where(net_bad, net_delay_slots, 0)
        down, back = was_up & ~up, ~was_up & up
        quit_wait = down & (mode == WAIT)
        quit_train = down & (mode == TRAIN)
        gone = int(np.count_nonzero(quit_wait))
        departures += gone
        recoveries += int(np.count_nonzero(back))
        k_lost = int(np.count_nonzero(quit_train))
        lost += k_lost
        drops += quit_train
        in_flight -= k_lost
        train_rem[quit_train] = zero
        mode[down] = OFF
        ret = back & (mode == OFF)
        mode[ret] = COOL
        cooldown[ret] = ready_delay + net_extra[ret]

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
        started += served

        # training (up users only): finishers push in user order
        tr = (mode == TRAIN) & up
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
            cooldown[fin] = ready_delay + net_extra[fin]
            idle_gap[fin] = zero

        # Eq. 10 energy; a device that is down draws nothing
        p = np.where(mode == TRAIN, p_busy, p_free)
        energy += np.where(up, p, zero) * t_d

        # Eqs. 15-16, departures leaving the request queue
        Q = max(Q - f(served) - f(gone), zero) + f(arrivals)
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
        "battery": battery.astype(np.float64), "drops": drops,
        "departures": departures, "recoveries": recoveries, "lost": lost,
        "started": started, "battery_down_peak": down_peak / max(n, 1),
        "battery_down_ever": float(np.mean(down_ever)) if n else 0.0,
        "net_bad_peak": net_peak / max(n, 1),
        "battery_falls": falls, "battery_rises": rises,
    }
