"""What the decode step needs to know about the lanes, resident on
the device and advanced by the step itself.

One int32 row a lane — pending token, pending position, the active
flag, temperature (its float32 bits), top_k, the block table — and the
sampling key live on the device between rounds (`LaneState.state`).
The `decode` program takes them (donated, like the pool), feeds them to
the model, and hands them back advanced: an active lane's pending token
becomes the token just sampled, its position grows by one, the key is
split where the host used to split it.  `prefill` reads its sampling
parameters and the key from the same state and writes the admitted
lane's whole row in the program.  A round therefore sends the device
nothing it already has.

What only the scheduler knows reaches the device as dirty rows.
`SlotScheduler.touched` names the lanes whose holder or block table
changed outside a step (admitted, released, preempted, a block
appended, a table entry swapped by copy-on-write; the engine adds the
lanes a verify round or a last prefill chunk advanced).  `sync()`
rebuilds those rows from their `Sequence`s into the host mirror and
uploads them as ONE packed array `[lanes, 1 + width]` (column 0 flags
the rows that count), which `decode` applies before it reads the state;
with nothing touched it hands back a patch that is already on the
device and flags no row, so the round uploads nothing.  The mirror is
the state the device will hold once the patch is applied, kept in step
by `advance()` after each fetch.

`chunk_prefill` and `spec_verify` keep host-built arguments: the chunk
step takes the key out of the state and hands its successor back, a
verify round consumes none, and the rows either advances are touched.
"""

from __future__ import annotations

from contextlib import contextmanager
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

#: columns of a lane's row; the block table fills the rest
TOKEN, CTX, ACTIVE, TEMPERATURE, TOP_K, TABLE = range(6)


# ----------------------------------------------------------------------
# inside the jitted steps
# ----------------------------------------------------------------------

def fields(rows):
    """(tokens, ctx_len, active, temperature, top_k, block tables) of
    rows `[..., width]`."""
    return (rows[..., TOKEN], rows[..., CTX], rows[..., ACTIVE] != 0,
            jax.lax.bitcast_convert_type(rows[..., TEMPERATURE],
                                         jnp.float32),
            rows[..., TOP_K], rows[..., TABLE:])


def patched(rows, patch):
    """`rows` with the flagged rows of `patch` in their place."""
    return jnp.where(patch[:, :1] != 0, patch[:, 1:], rows)


def advanced(rows, nxt):
    """`rows` after a decode step sampled `nxt`: on the active lanes
    the sampled token is pending, one position further on."""
    live = rows[:, ACTIVE] != 0
    rows = rows.at[:, TOKEN].set(jnp.where(live, nxt, rows[:, TOKEN]))
    return rows.at[:, CTX].add(live.astype(rows.dtype))


def split_request(request, width):
    """A prefill's one upload, `[slot | row | bucket-padded prompt]`,
    taken apart: (slot, row [width], tokens [1, bucket])."""
    return request[0], request[1:1 + width], request[None, 1 + width:]


def admitted(rows, slot, row, nxt):
    """`rows` with `row` in lane `slot`, the prefill's sampled token
    pending."""
    return jax.lax.dynamic_update_slice(
        rows, row.at[TOKEN].set(nxt)[None], (slot, jnp.int32(0)))


# ----------------------------------------------------------------------
# the host's side
# ----------------------------------------------------------------------

def placement(params, tp=None):
    """host array -> device array where the steps run, so that no step
    ever mixes arguments committed to different places (which would
    fork a second pjit cache entry and break zero-recompile):
    replicated on the mesh under tensor parallelism (`tp`, a
    `TensorParallelPlacement`); on the replica's own chip when the
    params are committed to one chip of a multi-chip host; uncommitted
    on the default device otherwise."""
    if tp is not None:
        return tp.put_replicated
    leaf = jax.tree_util.tree_leaves(params)[0]
    if getattr(leaf, "committed", False):
        return partial(jax.device_put, device=next(iter(leaf.devices())))
    return jax.device_put


class LaneState:
    """The device-resident lane rows and key, their host mirror, and
    the dirty-row upload (module docstring).  The engine's loop is the
    single caller."""

    def __init__(self, scheduler, seed: int, put, registry):
        self.scheduler = scheduler
        self._put = put           # host array -> where the steps run
        lanes = scheduler.max_slots
        self.width = TABLE + scheduler.max_blocks_per_seq
        #: what the device holds once the next patch is applied
        self.mirror = np.zeros((lanes, self.width), np.int32)
        self._no_patch = put(np.zeros((lanes, 1 + self.width), np.int32))
        self.state = {"rows": put(self.mirror),
                      "rng": put(jax.random.PRNGKey(seed))}
        self._c_rows = registry.counter(
            "generation_lane_rows_sent_total",
            help="lane rows uploaded to the device-resident lane state "
                 "(rows the scheduler changed outside a step)")
        self._c_syncs = registry.counter(
            "generation_lane_sync_rounds_total",
            help="decode rounds that uploaded a patch of lane rows "
                 "(the others sent the device nothing)")

    def _row(self, seq, out) -> None:
        """A running sequence's row, written into `out`."""
        out[TOKEN] = seq.generated[-1] if seq.generated \
            else seq.prompt[-1]
        out[CTX] = seq.context_len - 1        # the pending position
        self._describe(seq, out)

    @staticmethod
    def _describe(seq, out) -> None:
        out[ACTIVE] = 1
        out[TEMPERATURE] = np.float32(seq.temperature).view(np.int32)
        out[TOP_K] = seq.top_k
        out[TABLE:TABLE + len(seq.block_table)] = seq.block_table

    def sync(self, skip=frozenset()):
        """The patch for this decode round: the touched lanes' rows
        rebuilt from the scheduler, or the resident empty patch.  The
        lanes of `skip` (advanced by a verify round) sit this round
        out and stay touched, to rejoin the next."""
        sched = self.scheduler
        touched = sched.touched
        sitting = [s.slot for s in skip if s.slot is not None]
        touched.update(sitting)
        if not touched:
            return self._no_patch
        idx = sorted(touched)
        rows = self.mirror
        rows[idx] = 0
        for i in idx:
            seq = sched.slots[i]
            if seq is not None and seq.status == "running" \
                    and seq not in skip:
                self._row(seq, rows[i])
        patch = np.zeros((len(rows), 1 + self.width), np.int32)
        patch[idx, 0] = 1
        patch[idx, 1:] = rows[idx]
        touched.clear()
        touched.update(sitting)
        self._c_rows.inc(len(idx))
        self._c_syncs.inc()
        return self._put(patch)

    def advance(self, nxt) -> None:
        """The mirror after a decode step sampled `nxt` (`advanced`)."""
        rows = self.mirror
        live = rows[:, ACTIVE] != 0
        rows[live, TOKEN] = nxt[live]
        rows[:, CTX] += rows[:, ACTIVE]

    def ctx_sum(self) -> int:
        """Context tokens the active lanes attend over this round."""
        return int(self.mirror[:, CTX] @ self.mirror[:, ACTIVE])

    def prefill_request(self, seq, tokens, bucket: int):
        """The one array a prefill uploads, `[slot | row | prompt]`
        (`split_request`), and the row as the program will leave it
        but for the sampled token.  The program writes the whole row,
        so whatever was pending for the lane is dropped."""
        request = np.zeros(1 + self.width + bucket, np.int32)
        request[0] = seq.slot
        row = request[1:1 + self.width]
        row[CTX] = len(tokens)
        self._describe(seq, row)
        request[1 + self.width:1 + self.width + len(tokens)] = tokens
        self.scheduler.touched.discard(seq.slot)
        return self._put(request), row

    def landed(self, slot: int, row, nxt: int) -> None:
        """The mirror after a prefill wrote `row` with `nxt` pending."""
        self.mirror[slot] = row
        self.mirror[slot, TOKEN] = nxt

    def invalidate(self) -> None:
        """Every row is rebuilt and sent by the next round: after a
        step that failed between `sync()` and its fetch, and after
        warm-up's dummy dispatches."""
        self.scheduler.touched.update(range(len(self.mirror)))

    @contextmanager
    def guard(self):
        """Around a dispatch that takes the state: a failure leaves
        the device's rows unknown, so all of them are sent again."""
        try:
            yield
        except BaseException:
            self.invalidate()
            raise

    # -- warm-up -------------------------------------------------------

    def warm_request(self, bucket: int):
        """A prefill request that writes nothing but the null block:
        one token, an empty table, an inactive row in lane 0."""
        request = np.zeros(1 + self.width + bucket, np.int32)
        request[1 + CTX] = 1
        return self._put(request)

    def idle_patch(self):
        """A patch that puts every lane out of the round."""
        patch = np.zeros((len(self.mirror), 1 + self.width), np.int32)
        patch[:, 0] = 1
        return self._put(patch)

    def key(self):
        """The sampling key as the device holds it now (a fetch)."""
        return np.asarray(self.state["rng"])

    def restore(self, key) -> None:
        """Undo warm-up: the key as it was, every row to be resent."""
        self.state["rng"] = self._put(key)
        self.invalidate()
