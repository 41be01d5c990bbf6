"""Plain reference of the paper's LeNet-5 fleet, trained for real.

arXiv:2204.13878. Sec. VI: each device trains LeNet-5 with momentum SGD
(Eq. 1, local batch 20) on its shard, starting from the global model it
pulled, and the asynchronous server applies every push whole; the server
momentum v <- beta v + (1 - beta) (theta_old - theta_new) / eta gives the
norm that Eq. 4's gap is computed from. Sec. VII: the devices of Table II
run apps as given and Alg. 2 (Eqs. 15-16, 21-23) decides each slot who
starts training; the request queue, the staleness queue and the energy
follow ``fleet_online``, whose tables and in-slot replay this module
imports, with the trace-mode norm replaced by the server's real one.

Written from the paper and these rules alone: it imports nothing of the
program. The forward, the loss and ``jax.grad`` are straightforward
``jax.numpy`` in float32 under ``jax.default_matmul_precision("highest")``,
on the host's CPU backend whatever the default device: there every
product is a float32 product, where a TPU at its default precision rounds
the operands to bfloat16, and its compiler does not finish the float32
("highest") convolution gradients in any useful time.
``dtype`` is the precision in which every parameter and momentum vector,
the clients' and the server's, is held between steps, and
``schedule_dtype`` that of the schedule's own arithmetic (energy, times
left, gaps, queues), as ``fleet_online``'s ``dtype`` is: the reference
holds float32 and float64, and the control, the reference computed one
precision lower, bfloat16 for both.

Departures from the paper, all the program's as well:

- synthetic class-conditional images stand in for CIFAR-10, handed in
  with the initial weights; the shards are equal and IID;
- a local round is one epoch over the client's shard, in the order of
  ``jax.random.permutation(sub, n_i)``, where ``key, sub =
  jax.random.split(key)`` once per epoch on the key chain that starts at
  ``PRNGKey(user)``; the last ``n_i mod 20`` samples of the order are
  not trained; the local momentum starts at zero every round;
- a device pulls the global model in the slot it starts training and
  trains when it finishes (the result is the same: its training sees
  only what it pulled); finishers push in user order;
- Alg. 2's schedule gap uses the server's momentum norm at the start of
  the slot, and a push's logged gap the norm just before that push;
  ``simulate`` can be handed those norms (``decision_norms``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.fleet_online import (COOL, TABLE2, TRAIN, WAIT,
                                          _sequential)

LAYERS = ("conv1", "conv2", "fc1", "fc2", "fc3")


def logits(p, x):
    """LeNet-5 on (B, 32, 32, 3) NHWC images: two 5x5 valid convolutions
    (6 and 16 maps) each with ReLU and 2x2 max pooling, then 400-120-84-10
    dense layers with ReLU between them."""
    def conv(x, layer):
        y = jax.lax.conv_general_dilated(
            x, p[layer]["w"], (1, 1), "VALID",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        return jax.nn.relu(y + p[layer]["b"])

    def pool(x):
        b, h, w, c = x.shape
        return x.reshape(b, h // 2, 2, w // 2, 2, c).max(axis=(2, 4))

    x = pool(conv(x, "conv1"))
    x = pool(conv(x, "conv2")).reshape(x.shape[0], -1)
    x = jax.nn.relu(x @ p["fc1"]["w"] + p["fc1"]["b"])
    x = jax.nn.relu(x @ p["fc2"]["w"] + p["fc2"]["b"])
    return x @ p["fc3"]["w"] + p["fc3"]["b"]


def loss(p, x, y):
    """Mean cross-entropy of the batch."""
    logp = jax.nn.log_softmax(logits(p, x))
    return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=1))


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def _held(tree, dtype):
    return jax.tree.map(lambda a: a.astype(dtype), tree)


def _step(p, v, x, y, eta, beta):
    """One minibatch of Eq. 1: v <- beta v + (1 - beta) g, p <- p - eta v,
    each rounded to the held precision."""
    dtype = jax.tree.leaves(p)[0].dtype
    pf = _f32(p)
    g = jax.grad(loss)(pf, x, y)
    v = _held(jax.tree.map(lambda a, b: beta * a + (1 - beta) * b,
                           _f32(v), g), dtype)
    return _held(jax.tree.map(lambda a, b: a - eta * b, pf, _f32(v)),
                 dtype), v


_step_jit = jax.jit(_step, static_argnames=("eta", "beta"))


def _apply(server_p, server_v, new, eta, beta):
    """A push applied whole: the server momentum takes (old - new) / eta,
    and the norm is that of the momentum after it."""
    dtype = jax.tree.leaves(server_p)[0].dtype
    s = jax.tree.map(lambda o, n: (o - n) / eta, _f32(server_p), _f32(new))
    v = _held(jax.tree.map(lambda a, b: beta * a + (1 - beta) * b,
                           _f32(server_v), s), dtype)
    sq = sum(jnp.sum(jnp.square(a)) for a in jax.tree.leaves(_f32(v)))
    return new, v, jnp.sqrt(sq)


_apply_jit = jax.jit(_apply, static_argnames=("eta", "beta"))


def flat(tree) -> np.ndarray:
    """A LeNet parameter tree as one float64 vector, layers in order."""
    return np.concatenate([np.asarray(tree[k][w], np.float64).reshape(-1)
                           for k in LAYERS for w in ("w", "b")])


def simulate(device, app_sched, app_choice, images, labels, params0, *,
             V, L_b, epsilon, eta, beta, t_d, ready_delay, trace_every,
             batch_size, dtype=np.float32, schedule_dtype=np.float64,
             decision_norms=None, train_pushes=None):
    """Run the fleet over ``app_sched.shape[0]`` slots with real training.

    ``device``, ``app_sched`` and ``app_choice`` are as in
    ``fleet_online.simulate``; ``images`` (n_train, 32, 32, 3) and
    ``labels`` (n_train,) are split into ``len(device)`` equal contiguous
    shards, and ``params0`` is the initial global model (a dict of
    layers, each ``{"w", "b"}``). Returns the push log columns, the
    per-user energy and update counts, Q and H at every
    ``trace_every``-th slot, the server momentum norm after each push
    (``v_norms``), the global model after the first slot that applied a
    push (``params_first``, flattened) and at the end (``params``).

    ``decision_norms``, where given, are the server momentum norms after
    each push (a run's answer reports them) that Alg. 2 and the logged
    gaps read in place of the reference's own, push for push; past the
    last of them the reference's own are read. Rounding compounds from
    push to push, so late in a run two sound trainings part by percent,
    and a decision on its boundary may then go either way: with the norms
    given, the schedule is held to Alg. 2 exactly, while the norms
    themselves are held to the reference's training over the first pushes
    (``bench/compare_ml.py``).

    ``train_pushes``, where given, ends the training once that many
    pushes and the first slot that applied one have been trained: the
    rest of the run is the schedule alone, on ``decision_norms``, which
    must then cover every push. ``v_norms`` then holds the trained
    pushes' norms, and ``params`` is the model after the last of them."""
    f = np.dtype(schedule_dtype).type
    T, n = app_sched.shape
    dev = np.asarray(device)
    sd = schedule_dtype
    p_train = np.array([r[1] for r in TABLE2], sd)[dev]
    t_train = np.array([r[2] for r in TABLE2], sd)[dev]
    p_idle = np.array([r[3] for r in TABLE2], sd)[dev]
    p_app = np.array([[a[0] for a in r[4]] for r in TABLE2], sd)[dev]
    p_corun = np.array([[a[1] for a in r[4]] for r in TABLE2], sd)[dev]
    t_corun = np.array([[a[2] for a in r[4]] for r in TABLE2], sd)[dev]
    V, L_b, eps_s, eta_s, beta_s, t_d = (f(x) for x in
                                         (V, L_b, epsilon, eta, beta, t_d))
    zero, one = f(0), f(1)
    users = np.arange(n)

    shard = len(labels) // n
    B = int(batch_size)
    steps = shard // B
    cpu = jax.devices("cpu")[0]

    with jax.default_device(cpu), jax.default_matmul_precision("highest"):
        x_all = jax.device_put(np.asarray(images, np.float32), cpu)
        y_all = jax.device_put(np.asarray(labels, np.int32), cpu)
        keys = [jax.random.PRNGKey(u) for u in range(n)]

        def local_round(u, p):
            keys[u], sub = jax.random.split(keys[u])
            order = np.asarray(jax.random.permutation(sub, shard))
            v = _held(jax.tree.map(jnp.zeros_like, p), dtype)
            for k in range(steps):
                idx = u * shard + order[k * B:(k + 1) * B]
                p, v = _step_jit(p, v, x_all[idx], y_all[idx], eta, beta)
            return p

        server_p = _held(jax.tree.map(
            lambda a: jax.device_put(np.asarray(a), cpu), params0), dtype)
        server_v = _held(jax.tree.map(jnp.zeros_like, server_p), dtype)
        v_norm = zero
        pulled = [None] * n
        pushed = 0

        mode = np.full(n, COOL, np.int8)
        cooldown = np.zeros(n, np.int64)
        app = np.full(n, -1, np.int64)
        app_rem = np.zeros(n, sd)
        train_rem = np.zeros(n, sd)
        corun = np.zeros(n, bool)
        idle_gap = np.zeros(n, sd)
        pulled_at = np.zeros(n, np.int64)
        energy = np.zeros(n, sd)
        updates = np.zeros(n, np.int64)
        version = in_flight = 0
        Q = H = zero
        log, norms, trace_Q, trace_H = [], [], [], []
        params0_flat = flat(server_p)
        params_first = None

        def gap(vn, lag):
            """Eq. 4 with the linear-weight-prediction multiplier."""
            return eta_s * (one - beta_s ** np.asarray(lag, sd)) \
                / (one - beta_s) * vn

        for t in range(T):
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

            cool = mode == COOL
            cooldown[cool] -= 1
            joined = cool & (cooldown <= 0)
            mode[joined] = WAIT
            arrivals = int(np.count_nonzero(joined))

            # Alg. 2 for each waiting user, with the server's norm now
            widx = np.nonzero(mode == WAIT)[0]
            g_sched = gap(v_norm, in_flight + np.arange(len(widx) + 1))
            c_sched = V * p_busy[widx] * t_d - Q
            c_idle = V * p_free[widx] * t_d
            g_idle = idle_gap[widx] + eps_s
            if H == zero:
                go = c_sched <= c_idle
            else:
                go = _sequential(c_sched, c_idle, g_sched, g_idle, H)
            before = np.cumsum(go) - go
            gap_sum = np.sum(np.where(go, g_sched[before], g_idle), dtype=sd)
            start, stay = widx[go], widx[~go]
            idle_gap[stay] += eps_s
            corun[start] = has_app[start]
            train_rem[start] = np.where(has_app[start],
                                        t_corun[start, a[start]],
                                        t_train[start])
            mode[start] = TRAIN
            pulled_at[start] = version
            for u in start:
                pulled[u] = server_p
            in_flight += len(start)

            # finishers train from what they pulled and push in user order
            tr = mode == TRAIN
            train_rem[tr] -= t_d
            fin = np.nonzero(tr & (train_rem <= zero))[0]
            if len(fin):
                lags = version + np.arange(len(fin)) - pulled_at[fin]
                train = params_first is None or train_pushes is None \
                    or pushed < train_pushes
                pre = []
                for u in fin:
                    pre.append(v_norm)
                    if train:
                        trained = local_round(int(u), pulled[u])
                        server_p, server_v, vn = _apply_jit(
                            server_p, server_v, trained, eta, beta)
                        norms.append(float(vn))
                    pulled[u] = None
                    pushed += 1
                    given = decision_norms is not None \
                        and pushed <= len(decision_norms)
                    if not (given or train):
                        raise ValueError("no norm for push "
                                         f"{pushed}: decision_norms must "
                                         "cover the untrained pushes")
                    v_norm = f(decision_norms[pushed - 1] if given
                               else norms[-1])
                if params_first is None:
                    params_first = flat(server_p)
                log.append((np.full(len(fin), t), fin, lags,
                            gap(np.array(pre, sd), lags), corun[fin],
                            np.ones(len(fin))))
                version += len(fin)
                in_flight -= len(fin)
                updates[fin] += 1
                mode[fin] = COOL
                cooldown[fin] = ready_delay
                idle_gap[fin] = zero

            energy += np.where(mode == TRAIN, p_busy, p_free) * t_d
            Q = max(Q - f(len(start)), zero) + f(arrivals)
            H = max(H + gap_sum - L_b, zero)
            if t % trace_every == 0:
                trace_Q.append(float(Q))
                trace_H.append(float(H))

    cols = [np.concatenate([blk[k] for blk in log]) if log else
            np.zeros(0) for k in range(6)]
    return {
        "t": cols[0].astype(np.int64), "user": cols[1].astype(np.int64),
        "lag": cols[2].astype(np.int64), "gap": cols[3].astype(np.float64),
        "corun": cols[4].astype(bool), "weight": cols[5].astype(np.float64),
        "energy": energy.astype(np.float64), "updates": updates,
        "trace_Q": np.array(trace_Q), "trace_H": np.array(trace_H),
        "v_norms": np.array(norms, np.float64),
        "params0": params0_flat,
        "params_first": (np.zeros(0) if params_first is None
                         else params_first),
        "params": flat(server_p),
    }
