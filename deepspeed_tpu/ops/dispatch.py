"""Which form each kernel dispatch site selected, made observable.

``auto`` settings pick the Pallas kernel on TPU and the XLA reference form
elsewhere (the CPU tests need the reference forms), so a chip machine whose
TPU failed to initialise would quietly run the CPU forms. Every dispatch
site therefore reports its choice here. The Python that chooses runs while
JAX traces, so one :func:`record` is one selection per compiled program:
it bumps a process-wide counter, keeps the selection's detail and logs the
first occurrence of each ``(site, form)`` pair. ``chip_smoke.py`` asserts on
:func:`selected` and :func:`details`.

Sites and their forms:

====================  ====================================================
``attention``         ``flash`` | ``flash_sharded`` | ``sp`` |
                      ``chunked_stream`` | ``einsum``
``vocab_head``        ``fused_ce`` | ``loss_chunk``
``paged_decode``      ``paged_kernel`` | ``paged_kernel_sharded`` |
                      ``gather_einsum``
``paged_prefill``     ``flash`` | ``einsum``
``paged_block``       ``paged_kernel`` | ``gather_einsum``
``latent_prefill``    ``flash`` | ``einsum`` (a latent-attention layer's
                      prefill, the latent expanded to heads: the flash
                      kernel with the values padded to the keys' width |
                      the einsum with the two widths as they are)
``latent_decode``     ``latent_kernel`` | ``gather_einsum`` (its decode
                      step, the expansion absorbed: the Pallas kernel that
                      reads each live latent row once | XLA's gather of the
                      rows and two einsums)
``paged_decode_attention``  ``per_kv_head`` | ``block_diagonal`` (how the
                      paged kernel takes its products, from the static
                      shapes of a call: query rows a kv head, head size)
``kda_decode``        ``kda_kernel`` | ``slot_update`` (a linear-attention
                      layer's one-token state update: the Pallas kernel
                      over the live rows' slots | ``kda_recurrent_step``
                      over the layer's whole slice of the pool)
``ssd_decode``        ``mamba2_kernel`` | ``slot_gather`` (a Mamba-2 layer's
                      decode-step state update: the Pallas kernel over the
                      live rows' slots | XLA's gather, update and
                      scatter of the rows' states)
``experts``           ``grouped_kernel`` | ``dense`` | ``ragged`` (a no-drop
                      MoE layer's expert matmuls, from the rows of a call:
                      the Pallas kernel over the touched experts of the
                      layer stack in place, a paged program's calls of at
                      most 512 rows on one device (past 128 each expert
                      over its own rows: ``tm=`` in the detail) | every
                      held expert over every row |
                      ``jax.lax.ragged_dot`` over sorted rows,
                      from 1,536 rows on)
``flash_bwd_diag``    ``chunks`` | ``whole`` (how the flash backward kernels
                      take a diagonal block, from the static shapes of a
                      call, once a backward pass traced: walked in chunks
                      of ``flash_attention._DIAG_CHUNK`` rows / keys, only
                      the pairs on or below the diagonal computed | whole,
                      where the call is not causal, ``bq != bk`` or the
                      block is no two whole chunks)
``fused_ce_fwd``      ``lane_state`` (the fused cross-entropy forward: its
                      softmax state kept a lane wide, reduced across lanes
                      once a token tile; the detail holds the tiles it took
                      from D, N, V and the dtype, ``bt_fwd=`` the forward's
                      own beside the backward's ``bt=`` and ``bv=``)
``kernel/<name>``     ``compiled`` | ``interpret`` | ``jnp`` (one per Pallas
                      entry point; ``jnp`` = the kernel's plain-XLA twin)
====================  ====================================================
"""

from __future__ import annotations

import collections
import threading
from typing import Dict, Optional

from deepspeed_tpu.utils.logging import logger

_lock = threading.Lock()
_counts: "collections.Counter[str]" = collections.Counter()
_details: Dict[str, str] = {}


def record(site: str, form: str, detail: str = "") -> None:
    """Count one selection of ``form`` at ``site``; log it the first time."""
    key = f"{site}={form}"
    with _lock:
        _counts[key] += 1
        _details[key] = detail
        first = _counts[key] == 1
    if first:
        logger.info(f"dispatch: {key}" + (f" ({detail})" if detail else ""))


def selected() -> Dict[str, int]:
    """``{"site=form": times selected}`` since the last :func:`reset`."""
    with _lock:
        return dict(_counts)


def details() -> Dict[str, str]:
    """``{"site=form": detail of its newest selection}`` since the last
    :func:`reset` (the geometry a site chose, where it records one)."""
    with _lock:
        return dict(_details)


def reset() -> None:
    with _lock:
        _counts.clear()
        _details.clear()


def on_tpu() -> bool:
    import jax
    return jax.default_backend() == "tpu"


def resolve_interpret(kernel: str, interpret: Optional[bool]) -> bool:
    """The one ``interpret`` rule of the Pallas entry points: ``None``
    compiles on TPU and interprets elsewhere; an explicit value is honoured,
    except that interpreting on a TPU backend is an error (it would run the
    host emulation in place of the Mosaic kernel on the very machine the
    kernel exists for). Records the outcome under ``kernel/<kernel>``."""
    if interpret is None:
        interpret = not on_tpu()
    elif interpret and on_tpu():
        raise RuntimeError(
            f"{kernel}: interpret=True on a TPU backend — the Pallas kernel "
            "must compile here; use the jnp reference for comparisons")
    record(f"kernel/{kernel}", "interpret" if interpret else "compiled")
    return interpret
