"""The copy schedule of the decode state-update kernels
(``kda_decode_update.py``, ``mamba2_decode_update.py``): the live rows' slots
of a float32 state pool through VMEM in PHASES, one direction at a time.

Both kernels run a grid ``(rows,)`` over a pool that enters whole, where it
lies in HBM (``pl.ANY``), and is the call's input AND its output
(``input_output_aliases``). Rows are addressed ``row -> pool row`` through
scalar prefetch, the live rows FIRST (``live_rows``: a stable sort by "holds
the dummy slot"), in phases of R rows (``phase_rows``: ``_PHASE_BYTES`` of
state). A phase's states are brought to VMEM by the kernel's own
``make_async_copy``, one copy a row, updated there in place over the phase's
R grid steps, and copied back to where they came from. Two buffers take
turns, and the copies are ordered so that reads and writes never share the
HBM: while phase p is worked on, phase p - 1 goes out; when both are done,
phase p + 1 comes in. Measured on a v5e (PERF.md section 6, PR 32): the chip
writes at 644 GB/s and reads at 731, a round trip with both directions in
flight at once runs at 657, and one direction at a time in 16 MB turns at
692. An inactive row (slot 0, the dummy) issues no copy: the dummy and every
slot no live row holds are not touched. Distinct live rows hold distinct
slots, so no two copies meet.
"""

import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# State a PHASE moves: the rows whose states are read together, updated in
# VMEM and written back together, two phases' buffers in turn (the numbers
# above; by state size: ``benchmarks/kda_decode_bench.py`` at 4 MB a row,
# ``benchmarks/mamba2_decode_bench.py`` at 2 MB).
_PHASE_BYTES = 16 * 1024 * 1024


def phase_rows(B: int, row_bytes: int) -> int:
    """Rows a phase (R): as many as the budget holds, at least one."""
    return int(max(1, min(B, _PHASE_BYTES // row_bytes)))


def live_rows(slots, base, B: int):
    """The scalars the kernels prefetch: ``slots`` ``[B]`` each row's state
    slot (0, the dummy, for an inactive row), ``base`` the layer's first pool
    row -> (``order`` the rows with the live ones first, in row order,
    ``rows`` each row's pool row, ``n_live`` ``[1]``)."""
    slots = jnp.asarray(slots, jnp.int32).reshape(B)
    live = slots != 0
    order = jnp.argsort(jnp.logical_not(live), stable=True).astype(jnp.int32)
    n_live = jnp.sum(live, dtype=jnp.int32).reshape(1)
    return order, jnp.asarray(base, jnp.int32) + slots, n_live


def by_live_row(i, order, rows, n_live):
    """Index map of a row's vectors ``[B, a, b]``: grid step ``i`` takes the
    i-th live row's block. A step past the live rows names the last live
    row's block again: the pipeline fetches nothing for it."""
    return (order[jnp.minimum(i, jnp.maximum(n_live[0] - 1, 0))], 0, 0)


def phase_scratch(R: int, row_shape):
    """The schedule's scratch: two phases' states, and a DMA semaphore a
    buffer for the reads and for the writes."""
    return [pltpu.VMEM((2, R, *row_shape), jnp.float32),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,))]


def in_phases(i, n_live, order_ref, rows_ref, pool_in, pool_out, buf, rsem,
              wsem, update):
    """Grid step ``i`` of the schedule: where row ``i`` is live, open its
    phase if it is the phase's first, call ``update(ph, at)`` with the row's
    state in ``buf[ph % 2, at]``, and close the phase if it is its last."""
    R = buf.shape[1]
    ph, at = i // R, i % R              # the row's phase, its place in it

    def copies(phase, write, wait):
        """Start, or wait for, a phase's copies: one a LIVE row of it, the
        row's whole state, in (pool -> VMEM) or out (VMEM -> pool)."""
        for j in range(R):
            pos = phase * R + j

            @pl.when(pos < n_live)
            def _():
                row, turn = rows_ref[order_ref[pos]], phase % 2
                vm = buf.at[turn, j]
                cp = pltpu.make_async_copy(vm, pool_out.at[row], wsem.at[turn]) \
                    if write else \
                    pltpu.make_async_copy(pool_in.at[row], vm, rsem.at[turn])
                cp.wait() if wait else cp.start()

    @pl.when(jnp.logical_and(i == 0, n_live > 0))
    def _():
        copies(0, write=False, wait=False)

    @pl.when(i < n_live)
    def _():
        # a phase opens: its states have landed, and the phase before it,
        # updated by now, goes out while this one is worked on. Never a read
        # and a write in flight together.
        @pl.when(at == 0)
        def _():
            copies(ph, write=False, wait=True)

            @pl.when(ph > 0)
            def _():
                copies(ph - 1, write=True, wait=False)

        update(ph, at)

        # a phase closes: the phase before it has landed, so its buffer
        # takes the next phase's reads; the last phase goes out itself
        last = i + 1 == n_live

        @pl.when(jnp.logical_or(at == R - 1, last))
        def _():
            @pl.when(ph > 0)
            def _():
                copies(ph - 1, write=True, wait=True)

            @pl.when((ph + 1) * R < n_live)
            def _():
                copies(ph + 1, write=False, wait=False)

            @pl.when(last)
            def _():
                copies(ph, write=True, wait=False)
                copies(ph, write=True, wait=True)
