"""What the decode step needs to know about the lanes, resident on
the device and advanced by the step itself.

One int32 row a lane and the sampling key live on the device between
rounds (`LaneState.state`).  A row has two owners.  The step's columns
— pending token, pending position, the active flag, the new tokens the
lane may still produce — are advanced by the `decode` program itself
(donated, like the pool): an active lane's pending token becomes the
token just sampled, its position grows by one, its count falls by one,
and the lane whose count reaches 0 or whose sampled token is its `eos`
goes inactive there and then, its context length 0, so the step stops
a lane that is done without the host having seen its last token.  The key is split where
the host used to split it.  The scheduler's columns — `eos` (-1: none),
temperature (its float32 bits), top_k, the block table — only the host
changes.  `prefill` reads its sampling parameters and the key from the
same state and writes the admitted lane's whole row in the program.  A
round therefore sends the device nothing it already has, and needs
nothing from the host that depends on the round before it: the engine
enqueues round N+1 before it has fetched round N's tokens (engine.py).

What only the scheduler knows reaches the device as dirty rows.
`SlotScheduler.touched` names the lanes whose holder or block table
changed outside a step (admitted, released, preempted, a block
appended, a table entry swapped by copy-on-write; the engine adds the
lanes a verify round or a last prefill chunk advanced).  `sync()`
uploads them as ONE packed array `[lanes, 1 + width]`, which `decode`
applies before it reads the state.  Column 0 says how much of a row
counts: `WHOLE` where the host is exact (a lane with nothing in
flight: released, or rebuilt from its `Sequence` after a drain), or
`OWNED` — the scheduler's columns alone — for a lane with a round in
flight, whose `Sequence` lags the device's token and position by what
is not collected yet.  With nothing touched `sync()` hands back a
patch that is already on the device and flags no row, so the round
uploads nothing.

The mirror follows the device in two tenses: its scheduler's columns
are what the device holds once everything ENQUEUED has run, its step's
columns what the device held after the last dispatch COLLECTED
(`landed()`, `advance()`, in dispatch order), with the same arithmetic.
With nothing in flight the two are one and the mirror is the device's
rows.

A model with state layers (hybrid.py) has a third resident entry,
"recurrent": every lane's fixed-size state, found by the lane's slot
like its row (kv_cache.RecurrentStatePool).  `decode` rewrites the
live lanes' states in place and leaves a dead lane's alone; `prefill`
leaves the state after the prompt's real tokens in the admitted lane's
slot.  The host never reads or patches it.

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

#: columns of a lane's row; the block table fills the rest.  The step
#: owns the first four, the scheduler the others (module docstring)
TOKEN, CTX, ACTIVE, LEFT, EOS, TEMPERATURE, TOP_K, TABLE = range(8)
#: a patch's column 0: the row does not count, all of it does, or the
#: scheduler's columns alone
WHOLE, OWNED = 1, 2


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
    """`rows` with the flagged rows of `patch` in their place: a
    `WHOLE` row, or an `OWNED` row's scheduler columns."""
    flag = patch[:, :1]
    owned = jnp.arange(rows.shape[1]) >= EOS
    return jnp.where((flag == WHOLE) | ((flag == OWNED) & owned),
                     patch[:, 1:], rows)


def _stepped(xp, head, eos, nxt, moved):
    """The step's four columns `head` ([..., 4]; `xp`: jnp on the
    device, numpy for the mirror) after the live lanes sampled `nxt`:
    the token pending `moved` positions further on, one fewer to come,
    and the lane out of the next round where that was its last token
    or its `eos` — with no context left, so that the round it sits out
    before the host clears its row reads none of its blocks."""
    token, ctx, active, left = (head[..., c] for c in range(EOS))
    live = active != 0
    left = left - live
    done = live & ((left <= 0) | (nxt == eos))
    return xp.stack([xp.where(live, nxt, token),
                     xp.where(done, 0, ctx + live * moved),
                     xp.where(done, 0, active), left],
                    axis=-1).astype(head.dtype)


def advanced(rows, nxt):
    """`rows` after a decode step sampled `nxt`."""
    return rows.at[:, :EOS].set(
        _stepped(jnp, rows[:, :EOS], rows[:, EOS], nxt, 1))


def split_request(request, width):
    """A prefill's one upload, `[slot | row | bucket-padded prompt]`,
    taken apart: (slot, row [width], tokens [1, bucket])."""
    return request[0], request[1:1 + width], request[None, 1 + width:]


def admitted(rows, slot, row, nxt):
    """`rows` with `row` in lane `slot`, the prefill's sampled token
    pending — stepped like a decode's, but for the position, which a
    request's row already names."""
    head = _stepped(jnp, row[:EOS], row[EOS], nxt, 0)
    return jax.lax.dynamic_update_slice(
        rows, row.at[:EOS].set(head)[None], (slot, jnp.int32(0)))


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
    the dirty-row upload (module docstring): which columns of a row the
    step owns and which the scheduler, and that a lane with a round in
    flight (`Sequence.in_flight`) is patched by the scheduler's columns
    alone.  The engine's loop is the single caller."""

    def __init__(self, scheduler, seed: int, put, registry,
                 recurrent=None):
        self.scheduler = scheduler
        self._put = put           # host array -> where the steps run
        lanes = scheduler.max_slots
        self.width = TABLE + scheduler.max_blocks_per_seq
        #: the device's rows: the scheduler's columns as of the last
        #: dispatch enqueued, the step's as of the last one collected
        self.mirror = np.zeros((lanes, self.width), np.int32)
        self._no_patch = put(np.zeros((lanes, 1 + self.width), np.int32))
        self.state = {"rows": put(self.mirror),
                      "rng": put(jax.random.PRNGKey(seed))}
        if recurrent is not None:
            # a model with state layers: each lane's recurrent state
            # rides with its row (kv_cache.RecurrentStatePool), taken
            # and handed back by the same two programs
            self.state["recurrent"] = jax.tree_util.tree_map(
                put, recurrent)
        self._c_rows = registry.counter(
            "generation_lane_rows_sent_total",
            help="lane rows uploaded to the device-resident lane state "
                 "(rows the scheduler changed outside a step)")
        self._c_syncs = registry.counter(
            "generation_lane_sync_rounds_total",
            help="decode rounds that uploaded a patch of lane rows "
                 "(the others sent the device nothing)")

    def _row(self, seq, out) -> None:
        """A running sequence's row, written into `out`: only where
        nothing of the lane's is in flight, so that its `Sequence` is
        what the device holds."""
        out[TOKEN] = seq.generated[-1] if seq.generated \
            else seq.prompt[-1]
        out[CTX] = seq.context_len - 1        # the pending position
        out[ACTIVE] = 1
        out[LEFT] = seq.max_new_tokens - len(seq.generated)
        self._owned(seq, out)

    @staticmethod
    def _owned(seq, out) -> None:
        """The scheduler's columns of `seq`'s row."""
        out[EOS] = -1 if seq.eos_id is None else seq.eos_id
        out[TEMPERATURE] = np.float32(seq.temperature).view(np.int32)
        out[TOP_K] = seq.top_k
        out[TABLE:] = 0
        out[TABLE:TABLE + len(seq.block_table)] = seq.block_table

    def sync(self, skip=frozenset()):
        """The patch for this decode round: the touched lanes' rows
        rebuilt from the scheduler — whole, or the scheduler's columns
        alone where the lane has a round in flight — or the resident
        empty patch.  The lanes of `skip` (advanced by a verify round)
        sit this round out and stay touched, to rejoin the next."""
        sched = self.scheduler
        touched = sched.touched
        sitting = [s.slot for s in skip if s.slot is not None]
        touched.update(sitting)
        if not touched:
            return self._no_patch
        idx = sorted(touched)
        rows = self.mirror
        patch = np.zeros((len(rows), 1 + self.width), np.int32)
        for i in idx:
            seq = sched.slots[i]
            if seq is None or seq.status != "running" or seq in skip:
                rows[i] = 0
                patch[i, 0] = WHOLE
            elif seq.in_flight:
                self._owned(seq, rows[i])
                patch[i, 0] = OWNED
            else:
                self._row(seq, rows[i])
                patch[i, 0] = WHOLE
        patch[idx, 1:] = rows[idx]
        touched.clear()
        touched.update(sitting)
        self._c_rows.inc(len(idx))
        self._c_syncs.inc()
        return self._put(patch)

    def advance(self, nxt):
        """The mirror after the decode step now collected sampled
        `nxt` (`advanced`); hands back which lanes were live in it."""
        rows = self.mirror
        live = rows[:, ACTIVE] != 0
        rows[:, :EOS] = _stepped(np, rows[:, :EOS], rows[:, EOS], nxt, 1)
        return live

    def ctx_sum(self) -> int:
        """Context tokens the active lanes attend over this round."""
        return int(self.mirror[:, CTX] @ self.mirror[:, ACTIVE])

    def prefill_request(self, seq, tokens, bucket: int):
        """The one array a prefill uploads, `[slot | row | prompt]`
        (`split_request`), and the step's columns of the row as the
        program takes them, for `landed()`.  The program writes the
        whole row and steps it by the token it samples (`admitted`),
        so whatever was pending for the lane is dropped; the
        scheduler's columns are in the mirror from here on."""
        request = np.zeros(1 + self.width + bucket, np.int32)
        request[0] = seq.slot
        row = request[1:1 + self.width]
        row[CTX] = len(tokens)
        row[ACTIVE] = 1
        row[LEFT] = seq.max_new_tokens - len(seq.generated)
        self._owned(seq, row)
        request[1 + self.width:1 + self.width + len(tokens)] = tokens
        self.mirror[seq.slot, EOS:] = row[EOS:]
        self.scheduler.touched.discard(seq.slot)
        return self._put(request), row[:EOS].copy()

    def landed(self, slot: int, head, nxt: int) -> None:
        """The mirror after the prefill now collected wrote a row of
        step's columns `head` and sampled `nxt` (`admitted`)."""
        self.mirror[slot, :EOS] = _stepped(
            np, head, self.mirror[slot, EOS], nxt, 0)

    def invalidate(self) -> None:
        """Every row is rebuilt and sent by the next round: after a
        step that failed between `sync()` and its fetch (the engine
        collects or drops what was in flight first, so the rows go up
        whole), and after warm-up's dummy dispatches."""
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
