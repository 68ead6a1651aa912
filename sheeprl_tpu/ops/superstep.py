"""Fused training supersteps: K gradient steps in ONE jitted dispatch.

The off-policy loops (Dreamer-V3, SAC, DroQ) all share the same per-step
dispatch shape on the host: gather a replay batch, maybe refresh the target
network, split a key, call the jitted train step — one host round trip per
gradient step. At small model sizes those dispatch gaps dominate the train
window. A superstep moves the whole window into XLA: ``lax.scan`` over K
steps, the replay gather inside the scan body (the ring is static during a
train window, so reading it in-graph is sound), the EMA target update as a
``lax.cond`` on a carried step counter, and the per-step metric vectors
stacked on device so the window costs ONE dispatch and ONE fetch.

Carry discipline mirrors the host loops exactly so a superstep is
numerically equivalent to K sequential train calls:

- the key evolves as ``key, k = jax.random.split(key)`` per step — the same
  stream the host loop advances — and the evolved key is returned so the
  host stays in sync across fused/unfused windows;
- the target refresh runs BEFORE the step's train body, gated on the carried
  counter (``counter % freq == 0``), with the first-ever gradient step doing
  a ``tau=1.0`` hard copy;
- ``params`` (including the target) are carried but NOT donated — the repo
  invariant that param buffers stay alive for concurrent readers (async
  param streaming to the host player) holds inside the fused path too.
  Only ``aux`` (optimizer/moments state) is donated.

On a pure data-parallel mesh the whole superstep (scan included) runs under
``parallel.shard_map`` over ``fabric.data_axis``: params/opt carries stay
replicated (the train body ``pmean``s its gradients, matching the per-step
sharded path's reduction semantics), the replay context is sharded along the
env axis so every device samples and gathers shard-locally at fixed shapes,
and the per-step metric vectors are already ``pmean``-reduced by the train
body before the scan stacks them — the window is still ONE dispatch and ONE
(replicated) fetch, now spanning the slice.
"""

from __future__ import annotations

import warnings
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from sheeprl_tpu.obs.telemetry import telemetry_fused_fallback
from sheeprl_tpu.parallel.shard_map import shard_map

# decorrelates the in-graph replay draw from the train stream: the scan body
# hands ``gather`` the step's train key, and sampling gathers fold it with
# this salt so index noise and gradient noise never share a stream
SAMPLE_KEY_SALT = 0x5EED


def fold_sample_key(key: jax.Array, axis_name: Optional[str] = None) -> jax.Array:
    """Derive the replay-sampling key of one superstep iteration from its
    train key (see :data:`SAMPLE_KEY_SALT`). Inside a ``shard_map``ped
    superstep pass ``axis_name`` so the salted key is additionally folded
    with ``lax.axis_index`` — each device then draws its own batch shard
    from a decorrelated stream while the carried key stays replicated."""
    key = jax.random.fold_in(key, SAMPLE_KEY_SALT)
    if axis_name is not None:
        key = jax.random.fold_in(key, lax.axis_index(axis_name))
    return key


# ---------------------------------------------------------------------------
# Fused-fallback bookkeeping (warn once per reason per run + telemetry event)
# ---------------------------------------------------------------------------

_warned_fallback_reasons: set = set()


def reset_fused_fallback_warnings() -> None:
    """Re-arm the warn-once filter; the algo mains call this when a run
    starts so back-to-back in-process runs each warn again."""
    _warned_fallback_reasons.clear()


def fused_fallback(reason: str, detail: str) -> None:
    """Record that ``algo.fused_gradient_steps`` could not fuse this run and
    it dispatches per-step instead.

    Emits a structured ``fused_fallback`` telemetry event (always — so
    ``tools.report --dispatch-stats`` can report *why* a run shows zero fused
    windows) and raises a ``UserWarning`` exactly once per ``reason`` per
    run. Known reasons: ``"host_buffer"`` (SAC-family in-scan gather needs
    the device replay ring), ``"model_axis"`` (fused supersteps are pure
    data-parallel; GSPMD model sharding keeps the per-step path), and
    ``"multi_process"`` (the scan cannot span process boundaries).
    """
    telemetry_fused_fallback(reason, detail)
    if reason not in _warned_fallback_reasons:
        _warned_fallback_reasons.add(reason)
        warnings.warn(detail, UserWarning, stacklevel=3)


def pregathered(ctx: Any, key: jax.Array, step_index: jax.Array) -> Any:
    """Host-buffer fallback gather: ``ctx`` is a pytree of ``[K, ...]``
    arrays pre-gathered on the host (one batch per scan iteration); the scan
    body slices out batch ``step_index``. Ignores ``key`` — the indices were
    drawn by the buffer's own host RNG, exactly like the unfused path."""
    del key
    return jax.tree.map(lambda x: x[step_index], ctx)


def periodic_target_ema(
    counter: jax.Array,
    source_params: Any,
    target_params: Any,
    freq: int,
    tau: float,
) -> Any:
    """Target-network refresh on the host loop's schedule, in-graph:
    every ``freq``-th gradient step blends ``tau * source + (1-tau) * target``,
    and the very first gradient step of the run (``counter == 0``) hard-copies
    (``tau = 1.0``) — the reference Dreamer-V3 warm start. No-op (identity on
    ``target_params``) on all other steps via ``lax.cond``."""
    tau_eff = jnp.where(counter == 0, jnp.float32(1.0), jnp.float32(tau))

    def refresh(operands):
        src, tgt = operands
        return jax.tree.map(lambda s, t: tau_eff * s + (1 - tau_eff) * t, src, tgt)

    return lax.cond(
        (counter % freq) == 0,
        refresh,
        lambda operands: operands[1],
        (source_params, target_params),
    )


def make_superstep_fn(
    train_body: Callable[[Any, Any, Any, jax.Array], Tuple[Any, Any, jax.Array]],
    gather: Callable[[Any, jax.Array, jax.Array], Any],
    num_steps: int,
    *,
    pre_step: Optional[Callable[[Any, Any, jax.Array], Tuple[Any, Any]]] = None,
    mesh=None,
    data_axis: Optional[str] = None,
    ctx_spec=None,
    model_axis: Optional[str] = None,
    carry_specs: Optional[Tuple[Any, Any]] = None,
    check_finite: bool = False,
    aot_cache=None,
    cache_tag: str = "superstep",
    cache_fingerprint: Optional[str] = None,
):
    """Wrap one un-jitted gradient step into a donated ``jax.jit(lax.scan)``
    over ``num_steps`` steps.

    - ``train_body(params, aux, batch, key) -> (params, aux, metrics)`` — the
      raw single-gradient-step body (e.g. Dreamer's ``local_train`` with its
      arguments regrouped). ``params`` is every pytree that must survive the
      dispatch un-donated (network + target params); ``aux`` is the
      donate-safe remainder (optimizer states, moments).
    - ``gather(sample_ctx, key, step_index) -> batch`` — pure function that
      produces iteration ``step_index``'s replay batch inside the scan body.
      Use :func:`pregathered` for host-pre-gathered batches or an on-device
      draw over ``(bufs, pos, full)`` (see ``data.device_buffer``); sampling
      gathers must :func:`fold_sample_key` the key they receive.
    - ``pre_step(params, aux, counter) -> (params, aux)`` — optional hook run
      before each step's gather/train (the EMA target refresh,
      :func:`periodic_target_ema`).
    - ``mesh`` / ``data_axis`` / ``ctx_spec`` — pass all three on a pure
      data-parallel mesh to run the whole scan under ``shard_map`` over
      ``data_axis``. ``ctx_spec`` is the ``PartitionSpec`` pytree prefix for
      ``sample_ctx`` (the sharded replay ring's ``(P(axis), P(axis),
      P(axis))`` or a pre-gathered ``P(None, None, axis)`` batch stack);
      every carry stays replicated, so the ``train_body`` MUST ``pmean`` its
      gradients/metrics over ``data_axis`` and in-scan gathers must fold the
      sampling key with ``axis_name=data_axis``.
    - ``model_axis`` / ``carry_specs`` — the 2-D ``(data, model)`` path. Pass
      ``mesh``, the model axis name and ``carry_specs=(param_specs,
      aux_specs)`` (PartitionSpec trees matching ``params``/``aux`` —
      ``Fabric.match_partition_rules`` over the carry) to run the scan as a
      single GSPMD program instead of ``shard_map``: the jit's in/out
      shardings commit the carries to their model-axis layout and a
      ``with_sharding_constraint`` at the end of each scan body pins them
      there, so each device's W2 (and Adam/EMA twin) shard stays resident
      across all ``num_steps`` iterations — no per-step all-gather of full
      weights. ``ctx_spec`` shards the pre-gathered batch stack over
      ``data_axis`` (the in-scan device-ring gather is shard_map-only; use
      :func:`pregathered` here). The ``train_body`` must NOT ``pmean``
      (GSPMD global semantics — XLA inserts the reductions), matching the
      per-step model-axis train path.

    Returns a jitted ``superstep(params, aux, counter, sample_ctx, key) ->
    (params, aux, key, metrics)`` where ``counter`` is the run's cumulative
    gradient-step count entering the window (int32 scalar), ``key`` comes
    back evolved by ``num_steps`` splits, and ``metrics`` is the scan-stacked
    ``[num_steps, ...]`` per-step metric output, fetched once per window.

    ``check_finite=True`` (the resilience non-finite sentinel,
    ``resilience.check_finite``) appends a fifth output: a ``[num_steps]``
    boolean vector, ``finite[i]`` true iff every inexact leaf of step ``i``'s
    metrics AND post-update params was finite. Computed in-graph per step
    (:func:`sheeprl_tpu.resilience.all_finite`), so the window still costs
    one dispatch — the host only pays the check when it fetches metrics it
    already wanted.

    ``aot_cache`` (an :class:`~sheeprl_tpu.ops.aotcache.AotCache`) persists
    the fused-window *executable*: the first call per input signature
    deserializes it from the cache — or compiles once and stores it — so a
    preemption-resume (``resume_from=auto`` after exit 77) skips the largest
    single compile on its critical path. ``cache_tag`` names the entries and
    ``cache_fingerprint`` must digest every config constant baked into the
    train graph (:func:`~sheeprl_tpu.ops.aotcache.config_fingerprint` over
    the algo node) — same shapes under a changed learning rate must miss.
    The cache is strictly optional: any miss or corrupt entry degrades to
    the compile the un-cached path would have paid anyway.
    """
    if num_steps <= 0:
        raise ValueError(f"'num_steps' ({num_steps}) must be greater than 0")
    if model_axis is not None:
        if mesh is None or carry_specs is None:
            raise ValueError("model-axis supersteps need both 'mesh' and 'carry_specs'")
        if data_axis is not None:
            raise ValueError(
                "pass either 'data_axis' (pure-DP shard_map scan) or 'model_axis' "
                "(2-D GSPMD scan), not both — the GSPMD path shards the batch via "
                "'ctx_spec' and needs no axis name in the body"
            )

    from sheeprl_tpu.resilience.sentinel import all_finite

    _is_spec = lambda s: isinstance(s, P)
    carry_shardings = None
    if model_axis is not None:
        param_specs, aux_specs = carry_specs
        carry_shardings = tuple(
            jax.tree.map(lambda s: NamedSharding(mesh, s), specs, is_leaf=_is_spec)
            for specs in (param_specs, aux_specs)
        )

    def superstep(params, aux, counter, sample_ctx, key):
        def body(carry, step_index):
            params, aux, counter, key = carry
            if pre_step is not None:
                params, aux = pre_step(params, aux, counter)
            key, k_train = jax.random.split(key)
            batch = gather(sample_ctx, k_train, step_index)
            params, aux, metrics = train_body(params, aux, batch, k_train)
            if carry_shardings is not None:
                # pin the carries to their (data, model) layout every
                # iteration: without the constraint GSPMD is free to
                # re-replicate the updated params/opt-state between scan
                # steps, which is exactly the full-weight all-gather per
                # step this path exists to eliminate
                params = lax.with_sharding_constraint(params, carry_shardings[0])
                aux = lax.with_sharding_constraint(aux, carry_shardings[1])
            out = metrics
            if check_finite:
                # metrics catch NaN losses; params catch an Inf that reached
                # the weights while the reported losses still looked sane
                out = (metrics, all_finite((metrics, params)))
            return (params, aux, counter + 1, key), out

        (params, aux, _, key), out = lax.scan(
            body,
            (params, aux, jnp.asarray(counter, jnp.int32), key),
            jnp.arange(num_steps, dtype=jnp.int32),
        )
        if check_finite:
            metrics, finite = out
            return params, aux, key, metrics, finite
        return params, aux, key, out

    if model_axis is not None:
        # 2-D GSPMD scan: carries committed to their model-axis layout via
        # jit in/out shardings (so the compiled program keeps each W2 /
        # Adam / EMA shard device-resident across the window), batch stack
        # sharded per ctx_spec, counter/key/metrics replicated.
        replicated = NamedSharding(mesh, P())
        ctx_shardings = (
            jax.tree.map(lambda s: NamedSharding(mesh, s), ctx_spec, is_leaf=_is_spec)
            if ctx_spec is not None
            else replicated
        )
        param_shardings, aux_shardings = carry_shardings
        jitted = jax.jit(
            superstep,
            in_shardings=(param_shardings, aux_shardings, replicated, ctx_shardings, replicated),
            out_shardings=(
                (param_shardings, aux_shardings, replicated, replicated, replicated)
                if check_finite
                else (param_shardings, aux_shardings, replicated, replicated)
            ),
            donate_argnums=(1,),
        )
        return _maybe_cached(jitted, aot_cache, cache_tag, cache_fingerprint, mesh, num_steps, check_finite)

    if mesh is not None:
        if data_axis is None or ctx_spec is None:
            raise ValueError("sharded supersteps need both 'data_axis' and 'ctx_spec'")
        # carries (params/aux/counter/key) are replicated; only the replay
        # context is sharded. The train body's pmean keeps the replicated
        # out_specs sound, exactly like the per-step sharded train fns.
        superstep = shard_map(
            superstep,
            mesh,
            in_specs=(P(), P(), P(), ctx_spec, P()),
            out_specs=(P(), P(), P(), P(), P()) if check_finite else (P(), P(), P(), P()),
        )

    # donate only aux: params stay un-donated (concurrent readers — the async
    # param stream to the host player — may be in flight), and sample_ctx
    # holds the replay ring, which the env loop keeps writing after the window
    jitted = jax.jit(superstep, donate_argnums=(1,))
    return _maybe_cached(jitted, aot_cache, cache_tag, cache_fingerprint, mesh, num_steps, check_finite)


def _maybe_cached(jitted, aot_cache, cache_tag, cache_fingerprint, mesh, num_steps, check_finite):
    """Wrap the jitted superstep in the executable cache when one is
    configured (``fabric.aot_cache_dir``). Donation is unchanged: ``lower``
    only inspects avals, and the resolved ``Compiled`` donates ``aux`` on
    call exactly like the jitted original."""
    if aot_cache is None:
        return jitted
    from sheeprl_tpu.ops.aotcache import AotCachedFunction

    return AotCachedFunction(
        jitted,
        aot_cache,
        tag=cache_tag,
        fingerprint=cache_fingerprint,
        mesh=mesh,
        extra={"num_steps": int(num_steps), "check_finite": bool(check_finite)},
    )
