"""Jit'd public wrappers around the Pallas kernels.

This module is the one place that decides whether a kernel is interpreted
(``interpret_mode``): on a TPU backend every call site compiles to Mosaic;
on any other backend (the CPU test runs) the kernel body executes
step-by-step in Python, exercising the same BlockSpec tiling/indexing.  The
raw kernels take ``interpret`` as a required argument, so no caller falls
back to the interpreter without naming it.  A test that compiles for a
described TPU from a CPU process steers the choice by patching
``interpret_mode``.  ``impl="xla"`` callers bypass kernels entirely and use
:mod:`repro.kernels.ref` (that is what the dry-run lowers, keeping the
roofline numbers kernel-agnostic).

Every wrapper is a ``jax.custom_vjp``: ``pallas_call`` has no autodiff rule
here, so the forward runs the Pallas kernel and the backward runs the
paired reference backward from :mod:`repro.kernels.vjp` (hand-derived
recompute for attention/MoE, chunked-formulation VJP for the scans).  That
makes ``jax.grad`` flow through ``impl="pallas"``/``impl="chunked"`` call
sites, and it is what the conformance harness's gradient differential
tests (``repro.conformance``) exercise against the sequential oracles.
"""

from __future__ import annotations

import jax

from repro.kernels import ref as _ref
from repro.kernels import vjp as _vjp
from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.moe_gmm import moe_ffn as _moe_ffn
from repro.kernels.mamba2_scan import mamba2_scan as _mamba2
from repro.kernels.rwkv6_scan import rwkv6_scan as _rwkv6


def interpret_mode() -> bool:
    """True when Pallas kernels run interpreted (any non-TPU backend)."""
    return jax.default_backend() != "tpu"


def flash_attention(q, k, v, *, causal=True, window=0, softcap=0.0,
                    block_q=128, block_k=128):
    @jax.custom_vjp
    def fa(q, k, v):
        return _flash(q, k, v, causal=causal, window=window, softcap=softcap,
                      block_q=block_q, block_k=block_k,
                      interpret=interpret_mode())

    def fwd(q, k, v):
        return fa(q, k, v), (q, k, v)

    def bwd(res, dy):
        return _vjp.attention_bwd(*res, dy, causal=causal, window=window,
                                  softcap=softcap)

    fa.defvjp(fwd, bwd)
    return fa(q, k, v)


def rwkv6_scan(r, k, v, w, u, s0, *, chunk=128):
    @jax.custom_vjp
    def wkv(r, k, v, w, u, s0):
        return _rwkv6(r, k, v, w, u, s0, chunk=chunk,
                      interpret=interpret_mode())

    def fwd(r, k, v, w, u, s0):
        return wkv(r, k, v, w, u, s0), (r, k, v, w, u, s0)

    def bwd(res, cts):
        return _vjp.rwkv6_bwd(*res, cts, chunk=chunk)

    wkv.defvjp(fwd, bwd)
    return wkv(r, k, v, w, u, s0)


def mamba2_scan(x, dt, a_log, b, c, h0, *, chunk=128):
    @jax.custom_vjp
    def ssd(x, dt, a_log, b, c, h0):
        return _mamba2(x, dt, a_log, b, c, h0, chunk=chunk,
                       interpret=interpret_mode())

    def fwd(x, dt, a_log, b, c, h0):
        return ssd(x, dt, a_log, b, c, h0), (x, dt, a_log, b, c, h0)

    def bwd(res, cts):
        return _vjp.mamba2_bwd(*res, cts, chunk=chunk)

    ssd.defvjp(fwd, bwd)
    return ssd(x, dt, a_log, b, c, h0)


def moe_ffn(xe, wi_gate, wi_up, wo, *, block_c=128, block_f=128):
    @jax.custom_vjp
    def gmm(xe, wi_gate, wi_up, wo):
        return _moe_ffn(xe, wi_gate, wi_up, wo, block_c=block_c,
                        block_f=block_f, interpret=interpret_mode())

    def fwd(xe, wi_gate, wi_up, wo):
        return gmm(xe, wi_gate, wi_up, wo), (xe, wi_gate, wi_up, wo)

    def bwd(res, dy):
        return _vjp.moe_ffn_bwd(*res, dy)

    gmm.defvjp(fwd, bwd)
    return gmm(xe, wi_gate, wi_up, wo)


# re-exported oracles (impl="xla" path)
ref = _ref
