"""Buffer-pool tier: slot accounting for resident blocks (paper Sec. 4.2).

The pool owns everything measured in 4 KB slots: admission of preload
candidates under the capacity limit, release of slots when blocks finish
or are evicted, and the *early-stop* reuse-eviction decision (Sec. 4.5)
that kicks a block back to UNCACHED after it has been reactivated more
than ``early_stop`` consecutive times.

All methods are pure jnp functions of the carried ``used_slots`` scalar
and per-block masks, so they compose inside the engine's
``jax.lax.while_loop`` tick unchanged.

**Batch capacity modes (PR 6):** on the per-query batch plane every
query budgets its OWN ``pool_slots`` (each carries a private
``used_slots``), so batch peak residency is Q x ``pool_slots``. The
aggregated plane holds ONE real pool with cross-query admission — every
query's preload demand competes for the same slots and a resident block
serves all Q queries at once. The engine builds that pool with
capacity ``pool_slots`` under ``pool_mode='shared'`` (batch peak
residency == a solo run's), or Q x ``pool_slots`` under
``pool_mode='per_query'`` (memory parity with the per-query plane, for
apples-to-apples schedule comparisons). Admission/release/eviction
accounting is unchanged on the merged plane because a block is
*finished* only when NO query has active vertices left in it — the
aggregated active counts the scheduler feeds in already encode the
cross-query refcount.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.core.prefix import cumsum


class BufferPool:
    """Slot accounting over a fixed pool of ``slots`` 4 KB units.

    ``block_io`` is the per-scheduling-block I/O cost in slots (0 for
    memory-resident mini pseudo-blocks and tail blocks).
    """

    def __init__(self, slots: int, block_io: jnp.ndarray,
                 early_stop: int = 0):
        self.slots = int(slots)
        self.block_io = block_io
        self.early_stop = int(early_stop)

    # ------------------------------------------------------------------
    def free(self, used_slots: jnp.ndarray) -> jnp.ndarray:
        return self.slots - used_slots

    def in_bounds(self, used_slots) -> bool:
        """Capacity invariant: 0 <= used_slots <= slots. Admission and
        release must preserve this on every tick; the property suite
        checks it against the engine's ``used_slots`` trace."""
        u = np.asarray(used_slots)
        return bool(((u >= 0) & (u <= self.slots)).all())

    def admit(self, used_slots: jnp.ndarray, spans: jnp.ndarray,
              want: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
        """Greedy prefix admission of preload candidates.

        ``spans[i]`` slots are granted to candidate i while the running
        total fits in the free capacity. Returns ``(take, used_slots')``.
        """
        cum_sp = cumsum(spans * want)
        take = want & (cum_sp <= self.free(used_slots))
        return take, used_slots + jnp.sum(spans * take)

    def release(self, used_slots: jnp.ndarray,
                released: jnp.ndarray) -> jnp.ndarray:
        """Return the slots of every block in the ``released`` mask."""
        return used_slots - jnp.sum(self.block_io * released)

    # ------------------------------------------------------------------
    def reuse_evictions(self, b_reuse: jnp.ndarray, pulled: jnp.ndarray,
                        reactivated: jnp.ndarray
                        ) -> tuple[jnp.ndarray, jnp.ndarray]:
        """Early-stop reuse eviction (Sec. 4.5).

        Updates the consecutive-reuse counter (incremented on
        reactivation, reset when a pulled block exhausts its work) and
        flags blocks whose counter exceeds the threshold for eviction.
        Returns ``(evict, b_reuse')`` — the caller zeroes the counter of
        evicted blocks after applying the state transition.
        """
        b_reuse = jnp.where(reactivated, b_reuse + 1,
                            jnp.where(pulled, 0, b_reuse))
        if self.early_stop > 0:
            evict = reactivated & (b_reuse > self.early_stop)
        else:
            evict = jnp.zeros_like(reactivated)
        return evict, b_reuse
