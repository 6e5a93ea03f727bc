"""Plain reference of the T2DRL controller, written from the paper
(arXiv:2411.01458, Secs. 3-6, Table 2) and the configuration files under
``chipbench/configs``; it imports nothing of the system under test.

One edge cell at a time, in straightforward ``jax.numpy``: the environment
(Eqs. 1-10, 23, 32, 36-37), the diffusion actor (Eqs. 17-20) or the DDPG
MLP actor, the 2x256 critic, the DDQN cacher (Eq. 33), Adam and cyclic
replay buffers.  Every matrix product runs at ``Precision.HIGHEST`` in the
network dtype (float32); ``dtype=bfloat16`` gives the lower-precision
control, with parameters, activations and optimizer state in bfloat16.

Random draws follow the key derivation the system documents for its
episode scan (reset, frame, slot and update keys; cell ``i`` of a batch
uses ``fold_in(key, i)``), so reference and system see the same traffic,
noise and minibatches.  ``teacher`` arrays feed the system's own executed
actions back in, the way a served model's tokens are fed to its reference:
each executed slot action and frame cache action is first compared with
the reference's own choice, then executed, so one rounding-level
difference cannot fork the trajectory.
"""
from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
MB_BITS = 8e6


# -- configuration ---------------------------------------------------------------

def shapes(config: dict) -> dict:
    """Sizes and constants of one configuration file, in one flat dict."""
    env, t = config["env"], config["t2drl"]
    U, M = env["U"], env["M"]
    return {**env, **config["d3pg"], **config["ddqn"],
            "S": 4 * U + M, "A": 2 * U, "J": len(env["gammas"]),
            "L": t["L"], "warmup": t["warmup"], "actor": t["allocator"],
            "lr_actor": t["lr_actor"], "lr_critic": t["lr_critic"],
            "lr_ddqn": t["lr_ddqn"], "eps_start": t["eps_start"],
            "eps_end": t["eps_end"], "eps_decay": t["eps_decay_episodes"]}


def seed_key(seed: int):
    """PRNG key for any whole number up to 64 bits (the benchmark's seeds
    can exceed 32 bits)."""
    seed = int(seed)
    k = jax.random.PRNGKey(seed % 2**32)
    return jax.random.fold_in(k, (seed // 2**32) % 2**32)


def batch_keys(key, n: int):
    """Cell ``i`` of a batch: ``key`` for cell 0, ``fold_in(key, i)`` else."""
    if n == 1:
        return key[None]
    return jnp.stack([key] + [jax.random.fold_in(key, i) for i in range(1, n)])


def _frac(c, episode):
    return jnp.clip(jnp.float32(episode) / max(c["eps_decay"], 1), 0.0, 1.0)


def epsilon(c, episode):
    """Linear epsilon-greedy schedule over episodes."""
    return c["eps_start"] + (c["eps_end"] - c["eps_start"]) * _frac(c, episode)


def sigma(c, episode):
    """Exploration noise, annealed on the epsilon schedule to 0.02."""
    frac = _frac(c, episode)
    return (c["explore_sigma"] * (1.0 - frac) + 0.02 * frac).astype(jnp.float32)


# -- networks ----------------------------------------------------------------------

def dense(x, layer, dt):
    y = jnp.matmul(x.astype(dt), layer["w"].astype(dt), precision=HIGHEST)
    return y + layer["b"].astype(dt)


def mlp(layers, x, dt):
    for layer in layers[:-1]:
        x = jax.nn.relu(dense(x, layer, dt))
    return dense(x, layers[-1], dt)


def _normal_layers(key, dims, scaled_by_mul: bool):
    keys = jax.random.split(key, len(dims) - 1)
    out = []
    for k, i, o in zip(keys, dims[:-1], dims[1:]):
        z = jax.random.normal(k, (i, o))
        w = z * (1.0 / math.sqrt(i)) if scaled_by_mul else z / math.sqrt(i)
        out.append({"w": w.astype(jnp.float32), "b": jnp.zeros(o)})
    return out


def diffusion_schedule(c):
    L = c["L"]
    l = jnp.arange(1, L + 1, dtype=jnp.float32)
    betas = 1.0 - jnp.exp(-c["beta_min"] / L - (2 * l - 1) / (2 * L**2)
                          * (c["beta_max"] - c["beta_min"]))
    alphas = 1.0 - betas
    abar = jnp.cumprod(alphas)
    prev = jnp.concatenate([jnp.ones(1), abar[:-1]])
    return alphas, abar, (1.0 - prev) / (1.0 - abar) * betas


def time_embedding(l, dim):
    half = dim // 2
    freqs = jnp.exp(-math.log(1000.0) * jnp.arange(half) / half)
    ang = jnp.asarray(l, jnp.float32)[..., None] * freqs
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


def actor_raw(c, actor, s, key, dt):
    """Raw action in [0, 1]^A for states ``s`` (..., S)."""
    if c["actor"] == "ddpg":
        return 0.5 * (jnp.tanh(mlp(actor, s, dt)) + 1.0)
    A, L = c["A"], c["L"]
    alphas, abar, btilde = diffusion_schedule(c)
    lead = s.shape[:-1]
    kx, ke = jax.random.split(key)
    x = jax.random.normal(kx, lead + (A,)).astype(dt)
    noises = jax.random.normal(ke, (L,) + lead + (A,))
    for l_rev in range(L - 1, -1, -1):
        te = jnp.broadcast_to(time_embedding(float(l_rev + 1), c["time_dim"]),
                              lead + (c["time_dim"],))
        h = jnp.concatenate([x, s.astype(dt), te.astype(dt)], axis=-1)
        eps_hat = mlp(actor, h, dt)
        a, ab = alphas[l_rev].astype(dt), abar[l_rev].astype(dt)
        x = (x - (1 - a) / jnp.sqrt(1 - ab) * eps_hat) / jnp.sqrt(a)
        if l_rev > 0:   # the chain's i-th step draws noises[i], i = L-1-l_rev
            x = x + (jnp.sqrt(btilde[l_rev]).astype(dt)
                     * noises[L - 1 - l_rev].astype(dt))
    return 0.5 * (jnp.tanh(x) + 1.0)


def amend(raw, req, rho, U):
    """Project a raw action onto the bandwidth simplex and the cache-gated
    compute simplex (with the 0.01 bandwidth pseudo-count)."""
    b_t, xi_t = raw[..., :U] + 0.01, raw[..., U:]
    b = b_t / (jnp.sum(b_t, axis=-1, keepdims=True) + 1e-9)
    gate = jnp.take_along_axis(rho, req, axis=-1).astype(raw.dtype)
    xi = xi_t * gate / (jnp.sum(gate * xi_t, axis=-1, keepdims=True) + 1e-9)
    return b, xi


def critic(c, params, s, a, dt):
    return mlp(params, jnp.concatenate([s.astype(dt), a.astype(dt)], -1), dt)[..., 0]


# -- initial state -------------------------------------------------------------------

def make_models(key, c):
    ks = jax.random.split(key, 8)
    u = lambda k, lo, hi: jax.random.uniform(k, (c["M"],), minval=lo, maxval=hi)
    return {"a1": u(ks[0], 50.0, 100.0), "a2": u(ks[1], 100.0, 150.0),
            "a3": u(ks[2], 150.0, 200.0), "a4": u(ks[3], 1.0, 50.0),
            "b1": u(ks[4], 0.05, 0.5), "b2": u(ks[5], 1.0, 10.0),
            "c": u(ks[6], 2.0, 10.0),
            "d_op": u(ks[7], *c["d_op_mb"]) * MB_BITS}


def _adam(params, dt):
    z = lambda p: jnp.zeros(p.shape, dt)
    return {"mu": jax.tree.map(z, params), "nu": jax.tree.map(z, params),
            "step": jnp.int32(0)}


def init_cell(key, c, dt):
    """One cell's models, learners, optimizer state and empty buffers."""
    km, kq, kd = jax.random.split(key, 3)
    ka, kc = jax.random.split(kd)
    S, A, U, M = c["S"], c["A"], c["U"], c["M"]
    h = c["actor_hidden"]
    if c["actor"] == "ddpg":
        actor = _normal_layers(ka, [S] + [h] * c["actor_layers"] + [A], False)
    else:
        actor = _normal_layers(
            ka, [A + S + c["time_dim"]] + [h] * c["actor_layers"] + [A], True)
    crit = _normal_layers(kc, [S + A] + [c["critic_hidden"]] * c["critic_layers"]
                          + [1], False)
    q = _normal_layers(kq, [c["J"]] + [c["ddqn_hidden"]] * c["ddqn_layers"]
                       + [2 ** M], False)
    cast = lambda t: jax.tree.map(lambda x: x.astype(dt), t)
    actor, crit, q = cast(actor), cast(crit), cast(q)
    cap, fcap = c["replay"], c["ddqn_replay"]
    ebuf = {"s": jnp.zeros((cap, S)), "a": jnp.zeros((cap, A)),
            "r": jnp.zeros(cap), "s1": jnp.zeros((cap, S)),
            "req": jnp.zeros((cap, U), jnp.int32), "rho": jnp.zeros((cap, M)),
            "req1": jnp.zeros((cap, U), jnp.int32), "rho1": jnp.zeros((cap, M))}
    fbuf = {"s": jnp.zeros(fcap, jnp.int32), "a": jnp.zeros(fcap, jnp.int32),
            "r": jnp.zeros(fcap), "s1": jnp.zeros(fcap, jnp.int32)}
    return {"models": make_models(km, c),
            "actor": actor, "actor_t": actor, "critic": crit, "critic_t": crit,
            "opt_a": _adam(actor, dt), "opt_c": _adam(crit, dt),
            "q": q, "q_t": q, "opt_q": _adam(q, dt),
            "ebuf": ebuf, "eptr": jnp.int32(0), "esize": jnp.int32(0),
            "fbuf": fbuf, "fptr": jnp.int32(0), "fsize": jnp.int32(0)}


def init_batch(key, c, cells, dt):
    return jax.vmap(lambda k: init_cell(k, c, dt))(batch_keys(key, cells))


# -- environment ------------------------------------------------------------------------

def _markov(key, idx, P):
    return jax.random.categorical(key, jnp.log(jnp.asarray(P)[idx] + 1e-12))


def _requests(key, gamma_idx, c):
    gamma = jnp.asarray(c["gammas"])[gamma_idx]
    ranks = jnp.arange(1, c["M"] + 1, dtype=jnp.float32)
    return jax.random.categorical(key, -gamma * jnp.log(ranks), shape=(c["U"],))


def _positions(key, lam, c):
    k1, k2, k3 = jax.random.split(key, 3)
    U, A = c["U"], c["area"]
    uni = jax.random.uniform(k1, (U, 2), minval=0.0, maxval=A)
    conc = jnp.clip(A / 2 + 30.0 * jax.random.normal(k2, (U, 2)), 0.0, A)
    edge = jax.random.uniform(k3, (U, 2), minval=0.0, maxval=A)
    side = jax.random.randint(jax.random.fold_in(k3, 1), (U,), 0, 4)
    off = jax.random.uniform(jax.random.fold_in(k3, 2), (U,), minval=0.0,
                             maxval=15.0)
    bx = jnp.where(side == 0, off, jnp.where(side == 1, A - off, edge[:, 0]))
    by = jnp.where(side == 2, off, jnp.where(side == 3, A - off, edge[:, 1]))
    bnd = jnp.stack([bx, by], axis=-1)
    return jnp.where(lam == 0, uni, jnp.where(lam == 1, conc, bnd))


# The smallest positive Rayleigh power a float32 uniform u in [0, 1) can
# give, -log1p(-u) at u = 2^-23.  At u = 0 the draw is exactly 0, h is 0 and
# every rate and delay of that user is infinite; the reference takes this
# draw there instead, and no other draw changes.
SMALLEST_FADE = -math.log1p(-2.0 ** -23)


def _gain(key, pos, c):
    bs = jnp.array([c["area"] / 2, c["area"] / 2])
    dis_km = jnp.maximum(jnp.linalg.norm(pos - bs, axis=-1), 1.0) / 1000.0
    g = 10.0 ** ((-128.1 - 37.6 * jnp.log10(dis_km)) / 10.0)
    fade = jax.random.exponential(key, (pos.shape[0],))
    return g * jnp.maximum(fade, jnp.float32(SMALLEST_FADE))


def _draw_slot(key, env, c, new_lambda=True):
    kl, kp, kh, kr, kd, knext = jax.random.split(key, 6)
    lam = _markov(kl, env["lam"], c["P_lambda"]) if new_lambda else env["lam"]
    pos = _positions(kp, lam, c)
    return {**env, "key": knext, "lam": lam, "pos": pos,
            "h": _gain(kh, pos, c), "req": _requests(kr, env["gamma"], c),
            "d_in": jax.random.uniform(kd, (c["U"],), minval=c["d_in_mb"][0],
                                       maxval=c["d_in_mb"][1]) * MB_BITS}


def env_reset(key, c):
    kg, kl, ks = jax.random.split(key, 3)
    U = c["U"]
    env = {"key": ks, "gamma": jax.random.randint(kg, (), 0, c["J"]),
           "lam": jax.random.randint(kl, (), 0, len(c["P_lambda"])),
           "pos": jnp.zeros((U, 2)), "h": jnp.ones(U),
           "req": jnp.zeros(U, jnp.int32),
           "d_in": jnp.ones(U) * c["d_in_mb"][0] * MB_BITS,
           "rho": jnp.zeros(c["M"])}
    k, knext = jax.random.split(env["key"])
    return _draw_slot(k, {**env, "key": knext}, c, new_lambda=False)


def env_new_frame(env, c):
    k, kr, knext = jax.random.split(env["key"], 3)
    gamma = _markov(k, env["gamma"], c["P_gamma"])
    return {**env, "key": knext, "gamma": gamma, "req": _requests(kr, gamma, c)}


def slot_metrics(env, c, models, b, xi):
    """Eqs. (2)-(10): per-user delay, quality and utility."""
    cached = env["rho"][env["req"]]
    b = jnp.maximum(b, 1e-9)
    p_user, p_bs = 10 ** (c["p_user_dbm"] / 10), 10 ** (c["p_bs_dbm"] / 10)
    n0 = 10 ** (c["n0_dbm_hz"] / 10)
    r_up = b * c["W_up"] * jnp.log2(1.0 + p_user * env["h"] / (n0 * b * c["W_up"]))
    r_dw = c["W_dw"] * jnp.log2(1.0 + p_bs * env["h"] / (n0 * c["W_dw"]))
    d_up = env["d_in"] / r_up + (1.0 - cached) * env["d_in"] / c["r_bc"]
    m = env["req"]
    d_op = models["d_op"][m]
    d_dw = d_op / r_dw + (1.0 - cached) * d_op / c["r_cb"]
    steps = xi * c["L_steps"]
    a1, a2, a3, a4 = (models[k][m] for k in ("a1", "a2", "a3", "a4"))
    mid = a2 + (a4 - a2) / (a3 - a1) * (steps - a1)
    q_edge = jnp.where(steps <= a1, a2, jnp.where(steps >= a3, a4, mid))
    q = jnp.where(cached > 0, q_edge, a4)
    b1, b2 = models["b1"][m], models["b2"][m]
    d_gt = jnp.where(cached > 0, b1 * steps + b2, b1 * a3 + b2)
    d_tl = d_up + d_dw + d_gt
    G = c["alpha"] * d_tl + (1.0 - c["alpha"]) * q
    return {"G": G, "d_tl": d_tl, "quality": q, "cached": cached,
            "d_up": d_up, "d_dw": d_dw, "d_gt": d_gt}


def env_step(env, c, models, b, xi):
    m = slot_metrics(env, c, models, b, xi)
    viol = (m["d_tl"] > c["tau"]).astype(jnp.float32)
    r = -jnp.mean(m["G"] + viol * c["chi"])
    k, knext = jax.random.split(env["key"])
    return _draw_slot(k, {**env, "key": knext}, c), r, m


def observe(env, c, models):
    """Eq. (21), each part scaled to O(1)."""
    return jnp.concatenate([
        (jnp.log10(env["h"] + 1e-30) + 12.0) / 5.0,
        env["req"].astype(jnp.float32) / c["M"], env["rho"],
        env["d_in"] / (c["d_in_mb"][1] * MB_BITS),
        models["d_op"][env["req"]] / (c["d_op_mb"][1] * MB_BITS)])


def cache_vector(a_int, M):
    m = jnp.arange(1, M + 1)
    return ((a_int[..., None] // (2 ** (M - m))) % 2).astype(jnp.float32)


# -- learners ---------------------------------------------------------------------------

def adam(grads, opt, params, lr, dt):
    step = opt["step"] + 1
    b1c = (1.0 - 0.9 ** step.astype(jnp.float32)).astype(dt)
    b2c = (1.0 - 0.999 ** step.astype(jnp.float32)).astype(dt)
    mu = jax.tree.map(lambda m, g: (0.9 * m + 0.1 * g).astype(dt), opt["mu"], grads)
    nu = jax.tree.map(lambda v, g: (0.999 * v + 0.001 * g * g).astype(dt),
                      opt["nu"], grads)
    new = jax.tree.map(
        lambda p, m, v: (p - lr * (m / b1c) / (jnp.sqrt(v / b2c) + 1e-8)).astype(dt),
        params, mu, nu)
    return new, {"mu": mu, "nu": nu, "step": step}


def polyak(target, online, rate):
    return jax.tree.map(lambda t, o: ((1.0 - rate) * t + rate * o).astype(t.dtype),
                        target, online)


def d3pg_update(c, st, batch, key, dt):
    """Eqs. (24)-(29): critic TD step, then the actor's deterministic
    policy gradient through the whole reverse chain, then both targets."""
    U = c["U"]
    k_t, k_pi = jax.random.split(key)
    raw1 = actor_raw(c, st["actor_t"], batch["s1"], k_t, dt)
    b1, xi1 = amend(raw1, batch["req1"], batch["rho1"], U)
    a1 = jnp.concatenate([b1, xi1], -1)
    y_hat = jax.lax.stop_gradient(
        batch["r"] + c["omega"] * critic(c, st["critic_t"], batch["s1"], a1, dt))

    def critic_loss(p):
        y = critic(c, p, batch["s"], batch["a"], dt)
        return jnp.mean(0.5 * (y_hat - y) ** 2)

    g_c = jax.grad(critic_loss)(st["critic"])
    crit, opt_c = adam(g_c, st["opt_c"], st["critic"], c["lr_critic"], dt)

    def actor_loss(p):
        raw = actor_raw(c, p, batch["s"], k_pi, dt)
        b, xi = amend(raw, batch["req"], batch["rho"], U)
        return -jnp.mean(critic(c, crit, batch["s"], jnp.concatenate([b, xi], -1), dt))

    g_a = jax.grad(actor_loss)(st["actor"])
    actor, opt_a = adam(g_a, st["opt_a"], st["actor"], c["lr_actor"], dt)
    return {**st, "actor": actor, "critic": crit, "opt_a": opt_a, "opt_c": opt_c,
            "actor_t": polyak(st["actor_t"], actor, c["tau_target"]),
            "critic_t": polyak(st["critic_t"], crit, c["tau_target"])}


def ddqn_update(c, st, batch, dt):
    """Eq. (33): double-DQN target (online argmax, target evaluation)."""
    J = c["J"]
    s, s1 = jax.nn.one_hot(batch["s"], J), jax.nn.one_hot(batch["s1"], J)
    rows = jnp.arange(batch["a"].shape[0])

    def loss(q):
        y = mlp(q, s, dt)[rows, batch["a"]]
        a1 = jnp.argmax(mlp(q, s1, dt), axis=-1)
        y_hat = batch["r"] + c["ddqn_discount"] * mlp(st["q_t"], s1, dt)[rows, a1]
        return jnp.mean(0.5 * (jax.lax.stop_gradient(y_hat) - y) ** 2)

    g = jax.grad(loss)(st["q"])
    q, opt = adam(g, st["opt_q"], st["q"], c["lr_ddqn"], dt)
    return {**st, "q": q, "opt_q": opt,
            "q_t": polyak(st["q_t"], q, c["ddqn_tau_target"])}


def _sample(buf, size, key, n):
    idx = jax.random.randint(key, (n,), 0, jnp.maximum(size, 1))
    return jax.tree.map(lambda d: d[idx], buf)


# -- one training episode of one cell -------------------------------------------------

def episode(c, st, key, eps, sig, teach_a, teach_c, use_teacher, dt):
    """One episode of Algorithm 1 for one cell.

    ``teach_a`` (T*K, A) and ``teach_c`` (T,) are the executed slot and
    frame actions to replay when ``use_teacher``; otherwise the reference
    executes its own.  Returns the new state and per-slot / per-frame
    records of what the reference chose and observed."""
    U, M, T, K = c["U"], c["M"], c["T"], c["K"]
    cap = c["replay"]
    models = st["models"]
    k_env, key = jax.random.split(key)
    env = env_reset(k_env, c)

    def frame(carry, xs):
        st, env = carry
        k_frame, t = xs
        kf = jax.random.split(k_frame, 3)
        env = env_new_frame(env, c)
        qv = mlp(st["q"], jax.nn.one_hot(env["gamma"], c["J"]), dt).astype(jnp.float32)
        k1, k2 = jax.random.split(kf[0])
        rand = jax.random.randint(k1, (), 0, 2 ** M)
        explore = jax.random.uniform(k2, ()) < eps
        own_c = jnp.where(explore, rand, jnp.argmax(qv)).astype(jnp.int32)
        a_c = jnp.where(use_teacher, teach_c[t], own_c)
        rho = cache_vector(a_c, M)
        env = {**env, "rho": rho}
        size0 = st["esize"]
        s = observe(env, c, models)

        def slot(carry, xs):
            st, env, s = carry
            k_slot, k_in = xs
            ks = jax.random.split(k_slot, 4)
            raw = actor_raw(c, st["actor"], s, ks[0], dt).astype(jnp.float32)
            raw = jnp.clip(raw + sig * jax.random.normal(ks[1], raw.shape), 0.0, 1.0)
            b, xi = amend(raw, env["req"], env["rho"], U)
            own_a = jnp.concatenate([b, xi])
            a = jnp.where(use_teacher, teach_a[t * K + k_in], own_a)
            env1, r, m = env_step(env, c, models, a[:U], a[U:])
            s1 = observe(env1, c, models)
            item = {"s": s, "a": a, "r": r, "s1": s1, "req": env["req"],
                    "rho": env["rho"], "req1": env1["req"], "rho1": env1["rho"]}
            stored = jnp.minimum(size0 + k_in + 1, cap)
            st = jax.lax.cond(
                (stored > c["warmup"]) & (size0 > 0),
                lambda st: d3pg_update(
                    c, st, _sample(st["ebuf"], st["esize"], ks[2], c["batch"]),
                    ks[3], dt),
                lambda st: st, st)
            rec = {"s": s, "own_a": own_a, "r": r, "s1": s1,
                   "hit": jnp.mean(m["cached"]), "G": jnp.mean(m["G"]),
                   "delay": jnp.mean(m["d_tl"]), "quality": jnp.mean(m["quality"]),
                   "viol": jnp.mean((m["d_tl"] > c["tau"]).astype(jnp.float32))}
            return (st, env1, s1), (rec, item)

        (st, env, _), (recs, items) = jax.lax.scan(
            slot, (st, env, s), (jax.random.split(kf[1], K), jnp.arange(K)))
        idx = (st["eptr"] + jnp.arange(K)) % cap
        st = {**st, "ebuf": jax.tree.map(lambda d, x: d.at[idx].set(x),
                                         st["ebuf"], items),
              "eptr": (st["eptr"] + K) % cap,
              "esize": jnp.minimum(st["esize"] + K, cap)}
        storage_viol = (jnp.sum(rho * models["c"]) > c["C"]).astype(jnp.float32)
        fr = {"gamma": env["gamma"], "a_c": a_c, "own_c": own_c,
              "explore": explore, "rand": rand,
              "q_gap": (jnp.max(qv) - qv[a_c]) / (jnp.std(qv) + 1e-30),
              "r_frame": jnp.mean(recs["r"]) - storage_viol * c["Xi"],
              "storage_viol": storage_viol}
        return (st, env), (recs, fr)

    (st, env), (slots, frames) = jax.lax.scan(
        frame, (st, env), (jax.random.split(key, T), jnp.arange(T)))

    def frame_update(st, t):
        item = {"s": frames["gamma"][t], "a": frames["a_c"][t],
                "r": frames["r_frame"][t], "s1": frames["gamma"][t + 1]}
        fcap = c["ddqn_replay"]
        st = {**st, "fbuf": jax.tree.map(lambda d, x: d.at[st["fptr"]].set(x),
                                         st["fbuf"], item),
              "fptr": (st["fptr"] + 1) % fcap,
              "fsize": jnp.minimum(st["fsize"] + 1, fcap)}
        kb = jax.random.fold_in(key, t)
        st = jax.lax.cond(
            st["fsize"] > c["ddqn_batch"],
            lambda st: ddqn_update(
                c, st, _sample(st["fbuf"], st["fsize"], kb, c["ddqn_batch"]), dt),
            lambda st: st, st)
        return st, None

    st, _ = jax.lax.scan(frame_update, st, jnp.arange(T - 1))
    flat = lambda x: x.reshape((T * K,) + x.shape[2:])
    slots = jax.tree.map(flat, slots)
    stats = {"episode_reward": jnp.sum(slots["r"]),
             "mean_reward": jnp.mean(slots["r"]),
             "hit_ratio": jnp.mean(slots["hit"]), "utility": jnp.mean(slots["G"]),
             "delay": jnp.mean(slots["delay"]), "quality": jnp.mean(slots["quality"]),
             "deadline_viol": jnp.mean(slots["viol"]),
             "storage_viol": jnp.mean(frames["storage_viol"])}
    return st, {"slots": slots, "frames": frames, "stats": stats}


@functools.lru_cache(maxsize=None)
def _jitted(c_json: str, dt_name: str):
    """Jitted batch init and batched episode for one configuration and
    dtype, built once per process."""
    c, dt = json.loads(c_json), jnp.dtype(dt_name)

    def run(st, keys, eps, sig, teach_a, teach_c, use_teacher):
        return jax.vmap(
            lambda s, k, ta, tc: episode(c, s, k, eps, sig, ta, tc, use_teacher, dt)
        )(st, keys, teach_a, teach_c)

    init = jax.jit(lambda k, n: init_batch(k, c, n, dt), static_argnums=1)
    return init, jax.jit(run)


def train(c, seed: int, cells: int, episodes: int, dt=jnp.float32, teacher=None):
    """``episodes`` training episodes of ``cells`` independent cells from
    ``seed``, keyed as the benchmark keys the system (init from the first
    half of ``split(seed_key(seed))``, episode ``e`` from ``fold_in`` of the
    second half).  ``teacher``: optional ``{"a": (B, E*T*K, A), "c": (B,
    E*T)}`` executed actions to replay.  Returns the initial and final
    state and per-episode records (leaves lead with ``(E, B)``)."""
    k_init, k_train = jax.random.split(seed_key(seed))
    init, step = _jitted(json.dumps(c, sort_keys=True), jnp.dtype(dt).name)
    st = st0 = init(k_init, cells)
    TK, T = c["T"] * c["K"], c["T"]
    use = teacher is not None
    recs = []
    for e in range(episodes):
        keys = batch_keys(jax.random.fold_in(k_train, e), cells)
        if use:
            ta = teacher["a"][:, e * TK:(e + 1) * TK]
            tc = teacher["c"][:, e * T:(e + 1) * T]
        else:
            ta = jnp.zeros((cells, TK, c["A"]))
            tc = jnp.zeros((cells, T), jnp.int32)
        st, rec = step(st, keys, epsilon(c, e), sigma(c, e), ta, tc, use)
        recs.append(rec)
    recs = jax.tree.map(lambda *x: jnp.stack(x), *recs)
    return st0, st, recs


# -- greedy decisions ---------------------------------------------------------------

def decide(c, actor, env, models, key, dt=jnp.float32):
    """Greedy (noise-free) allocation ``(b, xi)`` for one slot state, and
    the raw compute share summed over the users whose model is cached
    (the normaliser of ``xi``)."""
    s = observe(env, c, models)
    raw = actor_raw(c, actor, s, key, dt).astype(jnp.float32)
    b, xi = amend(raw, env["req"], env["rho"], c["U"])
    gate = env["rho"][env["req"]]
    return b, xi, jnp.sum(gate * raw[c["U"]:])


def slot_pool(c, st, key, n: int, dt=jnp.float32):
    """``n`` consecutive slot states of one greedy episode from ``key``: the
    cache of each frame is the DDQN's greedy choice, each slot executes the
    greedy allocation.  ``st``: an unbatched cell state from ``init_cell``.
    Returns env dicts stacked over the ``n`` slots."""
    models, U, M, K = st["models"], c["U"], c["M"], c["K"]
    env = env_reset(key, c)

    def step(env, xs):
        i, k = xs
        env = jax.lax.cond(i % K == 0, lambda e: env_new_frame(e, c),
                           lambda e: e, env)
        qv = mlp(st["q"], jax.nn.one_hot(env["gamma"], c["J"]), dt)
        rho = jnp.where(i % K == 0, cache_vector(jnp.argmax(qv), M), env["rho"])
        env = {**env, "rho": rho}
        b, xi, _ = decide(c, st["actor"], env, models, k, dt)
        env1, _, _ = env_step(env, c, models, b, xi)
        return env1, env

    _, pool = jax.lax.scan(step, env, (jnp.arange(n),
                                       jax.random.split(jax.random.fold_in(key, 1), n)))
    return pool


@functools.lru_cache(maxsize=None)
def _decision_fns(c_json: str, dt_name: str):
    c, dt = json.loads(c_json), jnp.dtype(dt_name)
    init = jax.jit(lambda k: init_cell(k, c, dt))
    pool = jax.jit(lambda st, k, n: slot_pool(c, st, k, n, dt), static_argnums=2)
    many = jax.jit(jax.vmap(lambda actor, models, env, k: decide(
        c, actor, env, models, k, dt), in_axes=(None, None, 0, 0)))
    return init, pool, many


def decision_fns(c, dt=jnp.float32):
    """Jitted ``(init_cell(key), slot_pool(st, key, n), decide over a batch
    of (env, key))`` for one configuration and dtype, built once per
    process; weights are arguments, never compiled-in constants."""
    return _decision_fns(json.dumps(c, sort_keys=True), jnp.dtype(dt).name)
