"""Plain reference of the fleet twin: a greedy T2DRL policy replayed
against Poisson request traffic in C edge cells, one FIFO queue per
(cell, model), written from the twin's documented model (DESIGN.md §11,
``FleetCfg``); it imports nothing of the system under test.

The environment, the diffusion actor and the DDQN are
``chipbench.reference``'s, with their own weights from the seed.  Keys
follow the derivation the twin documents: cell ``i`` of a call with seed
``s`` takes ``fold_in(PRNGKey(s), i)`` (cell 0 the key itself); a cell
splits off its reset key, then one key per frame; a frame splits three
(the cache key, the slot keys' parent, unused); a slot key splits into the
reverse-chain key and the tick keys' parent, one tick key per tick.

Per (cell, model, tick), with unfinished work ``W`` and service time ``s``:

- ``n`` Poisson arrivals at rate ``arrivals_per_user_s * U * dt`` times the
  Zipf mix of the slot's popularity state, truncated at ``max_arrivals``
  (the excess counted in ``truncated``);
- a cached model admits up to ``floor(queue_cap - W / s)`` of them (the
  rest are dropped); the k-th admitted waits ``W + (k - 1) s`` and its
  latency is transmission + wait + ``s``; ``W`` then grows by the admitted
  work and drains by ``dt`` (Lindley);
- an uncached model takes the cloud path: every arrival is served at once,
  with no queue, in transmission + ``s``;
- ``s`` and the transmission time are the means, over the users who asked
  for the model in the slot, of their generation and uplink + downlink
  delays under the slot's greedy allocation; before a model is first asked
  for, the cloud's generation delay and the transmission at an equal
  bandwidth split over the reset slot's channels;
- latencies go into ``hist_bins`` equal bins on ``[0, hist_max)``, the
  last bin taking the overflow.

The frame's cache vector is given (``rho_table``, one per popularity
state): the program's, checked against the reference's DDQN by
``cache_q_gap`` and then executed, as the training reference's teacher
executes the program's actions.  ``dt=bfloat16`` gives the control: the
actor, the arrival rates and the queue arithmetic in bfloat16.
"""
from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import reference

COUNTS = ("arrivals", "admitted", "dropped", "truncated", "slo_viol",
          "deadline_miss")


def cache_vector_of(a_int, M):
    return np.asarray(reference.cache_vector(jnp.asarray(a_int), M))


def q_values(c, q, dt=jnp.float32):
    """The DDQN's Q-values of every cache action in each popularity state,
    ``(J, 2^M)``, in float32."""
    return reference.mlp(q, jax.nn.one_hot(jnp.arange(c["J"]), c["J"]),
                         dt).astype(jnp.float32)


def tick(work, n_raw, serv, trans, cached, f, dt_tick, tau):
    """One tick of every queue of one cell.  All arguments are ``(M,)``
    except the scalars; ``f`` is the traffic's fleet parameters.  Returns
    the new unfinished work, the tick's counts, the ``(M, A)`` latencies
    and waits of the arrival slots, and which of those slots were served."""
    A = f["max_arrivals"]
    dtype = work.dtype
    n = jnp.minimum(n_raw, A)
    depth = work / jnp.maximum(serv, 1e-6)
    room = jnp.floor(jnp.maximum(f["queue_cap"] - depth, 0.0))
    adm = jnp.where(cached, jnp.minimum(n, room), n)
    k = jnp.arange(1, A + 1, dtype=dtype)[None, :]
    served = k <= adm[:, None]
    wait = jnp.where(cached[:, None], work[:, None] + (k - 1) * serv[:, None],
                     jnp.zeros((), dtype))
    lat = trans[:, None] + wait + serv[:, None]
    counts = {
        "arrivals": jnp.sum(n).astype(jnp.int32),
        "admitted": jnp.sum(adm).astype(jnp.int32),
        "dropped": jnp.sum(jnp.where(cached, n - adm, 0)).astype(jnp.int32),
        "truncated": jnp.sum(n_raw - n).astype(jnp.int32),
        "slo_viol": jnp.sum(served & (lat > f["slo"])).astype(jnp.int32),
        "deadline_miss": jnp.sum(jnp.where(trans + serv > tau, adm, 0)
                                 ).astype(jnp.int32),
        "lat_sum": jnp.sum(jnp.where(served, lat, 0)),
        "wait_sum": jnp.sum(jnp.where(served, wait, 0)),
    }
    new = jnp.maximum(work + jnp.where(cached, adm * serv, 0) - dt_tick, 0)
    return new.astype(dtype), counts, lat, wait, served


def _per_model(req, values, M, dtype):
    """Sum of ``values`` over the users who asked for each model, and how
    many asked."""
    sums = jnp.zeros(M, dtype).at[req].add(values.astype(dtype))
    return sums, jnp.zeros(M, dtype).at[req].add(1)


def cell_episode(c, f, actor, models, rho_table, key, dt):
    """One cell's episode horizon: counts, ``(hist_bins,)`` histogram and
    the ``(T*K,)`` backlog (sum of unfinished work) after each slot."""
    U, M, T, K = c["U"], c["M"], c["T"], c["K"]
    ticks, bins, hmax = f["ticks_per_slot"], f["hist_bins"], f["hist_max"]
    dt_tick = c["tau"] / ticks
    k_env, key = jax.random.split(key)
    env = reference.env_reset(k_env, c)

    p_user, p_bs = 10 ** (c["p_user_dbm"] / 10), 10 ** (c["p_bs_dbm"] / 10)
    n0 = 10 ** (c["n0_dbm_hz"] / 10)
    share = 1.0 / U
    r_up0 = share * c["W_up"] * jnp.log2(
        1.0 + p_user * env["h"] / (n0 * share * c["W_up"]))
    r_dw0 = c["W_dw"] * jnp.log2(1.0 + p_bs * env["h"] / (n0 * c["W_dw"]))
    d_in_mean = 0.5 * (c["d_in_mb"][0] + c["d_in_mb"][1]) * reference.MB_BITS
    serv0 = models["b1"] * models["a3"] + models["b2"]
    trans0 = (jnp.mean(env["d_in"] / r_up0) + d_in_mean / c["r_bc"]
              + models["d_op"] * (jnp.mean(1.0 / r_dw0) + 1.0 / c["r_cb"]))
    ranks = jnp.arange(1, M + 1, dtype=jnp.float32)
    base_rate = (jnp.float32(f["arrivals_per_user_s"]) * jnp.float32(U)
                 * jnp.float32(1.0) * dt_tick)
    zero = {k: jnp.int32(0) for k in COUNTS}
    zero.update(lat_sum=jnp.zeros((), dt), wait_sum=jnp.zeros((), dt))

    def slot(carry, k_slot):
        env, work, serv, trans, counts, hist = carry
        ka, kt = jax.random.split(k_slot)
        b, xi, _ = reference.decide(c, actor, env, models, ka, dt)
        env1, _, m = reference.env_step(env, c, models, b, xi)
        gt, cnt = _per_model(env["req"], m["d_gt"], M, dt)
        tr, _ = _per_model(env["req"], m["d_up"] + m["d_dw"], M, dt)
        seen = cnt > 0
        serv = jnp.where(seen, gt / jnp.maximum(cnt, 1), serv)
        trans = jnp.where(seen, tr / jnp.maximum(cnt, 1), trans)
        gamma = jnp.asarray(c["gammas"])[env["gamma"]]
        rate = (base_rate * jax.nn.softmax(-gamma * jnp.log(ranks))).astype(dt)
        cached = env["rho"] > 0

        def one_tick(carry, k_tick):
            work, counts, hist = carry
            n_raw = jax.random.poisson(k_tick, rate).astype(dt)
            work, add, lat, _, served = tick(work, n_raw, serv, trans, cached,
                                             f, dt_tick, c["tau"])
            idx = jnp.clip((lat / hmax * bins).astype(jnp.int32), 0, bins - 1)
            hist = hist.at[idx.ravel()].add(served.astype(jnp.int32).ravel())
            counts = {k: counts[k] + add[k].astype(counts[k].dtype)
                      for k in counts}
            return (work, counts, hist), None

        (work, counts, hist), _ = jax.lax.scan(
            one_tick, (work, counts, hist), jax.random.split(kt, ticks))
        return (env1, work, serv, trans, counts, hist), jnp.sum(work)

    def frame(carry, k_frame):
        env, *rest = carry
        kf = jax.random.split(k_frame, 3)
        env = reference.env_new_frame(env, c)
        env = {**env, "rho": rho_table[env["gamma"]]}
        carry, backlog = jax.lax.scan(slot, (env, *rest),
                                      jax.random.split(kf[1], K))
        return carry, backlog

    init = (env, jnp.zeros(M, dt), serv0.astype(dt), trans0.astype(dt), zero,
            jnp.zeros(bins, jnp.int32))
    (_, _, _, _, counts, hist), backlog = jax.lax.scan(
        frame, init, jax.random.split(key, T))
    return counts, hist, backlog.reshape(T * K)


@functools.lru_cache(maxsize=None)
def _jitted(c_json: str, f_json: str, dt_name: str):
    c, f, dt = json.loads(c_json), json.loads(f_json), jnp.dtype(dt_name)
    return jax.jit(jax.vmap(
        lambda actor, models, rho_table, key: cell_episode(
            c, f, actor, models, rho_table, key, dt),
        in_axes=(None, None, None, 0)))


def call_keys(seed: int, cells: int):
    """The cell keys of one twin call with seed ``seed``."""
    return reference.batch_keys(jax.random.PRNGKey(int(seed)), cells)


def fleet(c, f, st, rho_table, seed: int, cells: int, dt=jnp.float32) -> dict:
    """The reference's summary of one call of ``cells`` cells with seed
    ``seed``: the fleet's counts, latency and wait sums, histogram and the
    ``(cells, T*K)`` backlog curve.  ``st``: the reference's cell state
    (``reference.init_cell``); ``rho_table``: ``(J, M)`` cache vectors."""
    run = _jitted(json.dumps(c, sort_keys=True), json.dumps(f, sort_keys=True),
                  jnp.dtype(dt).name)
    actor = jax.tree.map(lambda x: x.astype(dt), st["actor"])
    counts, hist, backlog = jax.device_get(run(
        actor, st["models"], jnp.asarray(rho_table, jnp.float32),
        call_keys(seed, cells)))
    out = {k: int(np.sum(counts[k])) for k in COUNTS}
    out.update(lat_sum=float(np.sum(counts["lat_sum"], dtype=np.float64)),
               wait_sum=float(np.sum(counts["wait_sum"], dtype=np.float64)),
               hist=np.asarray(hist).sum(axis=0),
               backlog_curve=np.asarray(backlog, np.float64))
    return out
