"""Jitted public wrappers over the Pallas kernels.

``interpret`` defaults to True on CPU (the validation mode of this
container) and False on real TPU backends.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from . import flash_attention as _fa
from . import partition as _part
from . import rwkv_scan as _rwkv
from . import segment_matmul as _segmm


_default_interpret = _part.default_interpret


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k"))
def flash_attention(q, k, v, *, causal: bool = True,
                    block_q: int = 128, block_k: int = 128):
    """q/k/v: [B, H, S|T, hd] (repeat KV heads for GQA before the call)."""
    return _fa.flash_attention(q, k, v, causal=causal, block_q=block_q,
                               block_k=block_k,
                               interpret=_default_interpret())


@jax.jit
def rwkv_scan(r, k, v, w, u, state0=None):
    """RWKV6 recurrence: [B,H,T,hd] -> (out, final state)."""
    return _rwkv.rwkv_scan(r, k, v, w, u, state0,
                           interpret=_default_interpret())


@functools.partial(jax.jit, static_argnames=("block_n",))
def partition(keys, counters, weights, cdf=None, *, block_n: int = 512):
    """Routing-table partition: (dest [N], histogram [W]).

    ``cdf`` optionally supplies the host-computed float32 row-CDF
    (``RoutingTable.cdf32``) for bit-exact host/device agreement.
    """
    return _part.partition(keys, counters, weights, cdf=cdf,
                           block_n=block_n, interpret=_default_interpret())


@functools.partial(jax.jit, static_argnames=("block_n",))
def partition_scatter(keys, counters, weights, cdf=None, *,
                      block_n: int = 512):
    """Fused exchange: (dest [N], within-destination rank [N], hist [W]).

    The rank output turns the scatter into a fancy-index placement at
    ``exclusive_cumsum(hist)[dest] + rank`` — no host sort.
    """
    return _part.partition_scatter(keys, counters, weights, cdf=cdf,
                                   block_n=block_n,
                                   interpret=_default_interpret())


@functools.partial(jax.jit, static_argnames=("block_n",))
def partition_scatter_fold(keys, counters, vals, weights, valid=None,
                           cdf=None, *, block_n: int = 512):
    """Fully fused exchange + downstream fold (device-resident plane).

    (dest [N], rank [N], hist [W], fold_counts [K], fold_sums [K]) in one
    kernel pass: partition, within-destination rank *and* the chunk's
    per-key GroupByAgg bincount fold, with ``valid`` masking the dead
    lanes of padded device chunks.
    """
    return _part.partition_scatter_fold(keys, counters, vals, weights,
                                        valid=valid, cdf=cdf,
                                        block_n=block_n,
                                        interpret=_default_interpret())


@functools.partial(jax.jit, static_argnames=("emit_width",))
def match_expand(wk, wv, wmask, mcounts, *, emit_width: int):
    """Hash-join probe expansion of a ``[W, B]`` pop window.

    Each live lane is repeated ``mcounts[w, key]`` times (owned +
    scattered build rows) into a padded, masked ``[W, emit_width]``
    output — the device plane's probe-expand step, exposed standalone
    for oracle tests and ad-hoc use.  Pure jnp (gather + vmapped binary
    search; no Pallas kernel: the expansion is memory-bound indexing
    with no reduction to fuse).
    """
    from . import ref as _ref
    return _ref.match_expand(wk, wv, wmask, mcounts, emit_width)


@functools.partial(jax.jit, static_argnames=("block_m", "block_n", "block_k"))
def segment_matmul(x, w, *, block_m: int = 128, block_n: int = 128,
                   block_k: int = 128):
    """Grouped expert matmul: [E,C,D] @ [E,D,F] -> [E,C,F]."""
    return _segmm.segment_matmul(x, w, block_m=block_m, block_n=block_n,
                                 block_k=block_k,
                                 interpret=_default_interpret())


@jax.jit
def skew_test(phi_l, phi_c, eta, tau):
    """Jitted twin of :func:`repro.core.skew_test.skew_test`.

    Scalar/vector boolean: worker ``l`` is overloaded relative to ``c``.
    Used in-dispatch by the device-resident controller
    (:mod:`repro.dataflow.device`); exposed standalone for oracle tests.
    """
    from . import ref as _ref
    return _ref.skew_test(phi_l, phi_c, eta, tau)


@jax.jit
def phase2_split(f_s, f_h):
    """Jitted single-helper phase-2 split ratio (load_transfer twin).

    Returns the fraction of the skewed worker's future share routed to
    the helper under the paper's fair-share rule, bit-exact against
    ``phase2_fractions_multi`` for the one-helper case.
    """
    from . import ref as _ref
    return _ref.phase2_fraction(f_s, f_h)


@jax.jit
def adjust_tau(phi_s, phi_h, eps, tau, eta, eps_lower, eps_upper,
               tau_increase, enabled):
    """Jitted twin of :func:`repro.core.adaptive_tau.adjust_tau`.

    Returns ``(new_tau, changed, decreased)``.
    """
    from . import ref as _ref
    return _ref.adjust_tau(phi_s, phi_h, eps, tau, eta=eta,
                           eps_lower=eps_lower, eps_upper=eps_upper,
                           tau_increase=tau_increase, enabled=enabled)


@jax.jit
def routing_consts(weights):
    """Jitted derived routing consts (cdf32/primary/is_split).

    Bit-exact sequential twin of ``RoutingTable._refresh_derived`` — see
    :func:`repro.kernels.ref.saturated_cdf32_seq`.
    """
    from . import ref as _ref
    return _ref.routing_consts(weights)
