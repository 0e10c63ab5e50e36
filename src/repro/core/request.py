"""Request lifecycle + phase state machine (paper §5.2 control plane).

A request iterates over denoising steps, alternating **Refresh** and
**Reuse** phases. Phase is derived from the cache policy: the first step of
every block refreshes (block transition), and a fixed ``refresh_interval``
forces periodic refreshes inside a block (the K_int cadence of §2.3).

Lifecycle (the robustness layer, ``docs/robustness.md``)::

    WAITING --admit--> RUNNING --all blocks done--> FINISHED
       |  ^               |
       |  '---preempt-----'      (rollback_block + tail requeue; repeatable
       |                          up to ServeConfig.max_preemptions)
       +--deadline expired--> SHED       (Outcome.SHED_DEADLINE / SHED_QUEUE)
       +--never admittable--> REJECTED   (Outcome.REJECTED_* + .error)

Terminal states always carry a structured :class:`Outcome`; REJECTED
additionally carries a human-readable ``error``. Preemption is NOT terminal:
the request rolls its active block back to all-mask and re-enters the
waiting queue, so its next step is a normal Refresh and the block's
denoising trajectory replays bit-identically (the preemption oracle).
"""
from __future__ import annotations

import enum
import math
from dataclasses import dataclass, field
from typing import Optional

import numpy as np

from repro.configs.base import ServeConfig
from repro.core import diffusion


class State(enum.Enum):
    WAITING = "waiting"
    RUNNING = "running"
    FINISHED = "finished"
    SHED = "shed"            # terminal: dropped by deadline/backpressure policy
    REJECTED = "rejected"    # terminal: never admittable (oversized/queue full)


class Outcome(enum.Enum):
    """Structured terminal outcome (EngineStats conservation law:
    ``submitted == finished + shed + rejected``)."""
    FINISHED = "finished"
    REJECTED_OVERSIZED = "rejected_oversized"      # can never fit the budget
    REJECTED_QUEUE_FULL = "rejected_queue_full"    # bounded queue, reject-new
    SHED_DEADLINE = "shed_deadline"                # deadline expired waiting
    SHED_QUEUE = "shed_queue"                      # bounded queue, evict-oldest


class Phase(enum.Enum):
    REFRESH = "refresh"
    REUSE = "reuse"


@dataclass
class Request:
    rid: int
    prompt: np.ndarray                  # [P] int32
    gen_len: int
    arrival: float                      # seconds (trace time)
    cfg: ServeConfig
    mask_id: int = 0
    # modality-frontend stub (vlm/audio): precomputed patch/frame embeddings
    # occupying the first ``frontend_len`` positions of the request's full
    # sequence. None for text-only archs. The frontend rows are REAL compute
    # in every Refresh — they count as query tokens and as packed-stream rows
    # (the fixed-length segment prefix of the flattened engine).
    frontend: Optional[np.ndarray] = None   # [F, frontend_dim] float32
    # absolute trace-time deadline (inf = none). Deadline-expired WAITING
    # requests are shed with Outcome.SHED_DEADLINE; residents always run to
    # completion (shedding in-flight work would waste its compute).
    deadline: float = math.inf

    state: State = State.WAITING
    # -- control-plane mirror of the active block (docs/engine.md) ---------
    # ``masked_left`` tracks how many positions of the active block are
    # still masked WITHOUT reading token values. ``diffusion.commit_tokens``
    # unmasks exactly ``min(n_commit, masked)`` positions (committed ids are
    # never the mask id — remapped), so this counter evolves deterministically
    # from lengths/config alone. It is what lets the pipelined engine advance
    # the state machine (block completion, phase transitions, FINISHED) at
    # dispatch time while the committed token VALUES are still in flight on
    # device. Kept exactly equal to ``block_masked()`` whenever no commit is
    # pending (asserted by the pipeline bit-identity suite).
    masked_left: int = 0
    # bumped by every rollback: an in-flight commit whose recorded epoch no
    # longer matches is stale (the block was preempted under it) and its
    # token values must be dropped on sync — the rollback already booked the
    # discarded commits as recompute debt.
    commit_epoch: int = 0
    slot: Optional[int] = None
    # generation of ``slot`` at allocation time (KVPool.take). A mismatch
    # against the pool's live counter means the slot was freed and recycled
    # under this request — the engine raises instead of gathering stale KV.
    slot_gen: Optional[int] = None
    tokens: Optional[np.ndarray] = None  # [max_seq_len]
    block_idx: int = 0
    step_in_block: int = 0
    steps_done: int = 0
    # robustness bookkeeping
    n_preempted: int = 0                 # times preempted (capped by config)
    recomputed_tokens: int = 0           # commits discarded by rollbacks
    outcome: Optional[Outcome] = None    # terminal outcome (None while live)
    error: Optional[str] = None          # per-request error on rejection
    # metrics, on the engine's run clock: admission when planned; the first
    # commit and the finish when their values reach the host (wall clock)
    # or when dispatched (modeled clock)
    t_admitted: float = -1.0
    t_first_commit: float = -1.0
    t_finished: float = -1.0

    def __post_init__(self):
        pad = (-self.gen_len) % self.cfg.block_size
        self.gen_len += pad
        # oversized geometry stays constructable (tokens=None) so admission
        # control can return the request with a structured REJECTED_OVERSIZED
        # outcome instead of asserting in the constructor — the owner must
        # reject it (budgeting.admission_block_reason) before scheduling it.
        if self.total_len <= self.cfg.max_seq_len:
            self.tokens = diffusion.build_sequence(
                self.prompt, self.gen_len, self.cfg.max_seq_len, self.mask_id)
        # a fresh block region is all-mask by construction
        self.masked_left = self.cfg.block_size

    # -- geometry ----------------------------------------------------------
    @property
    def prompt_len(self) -> int:
        return len(self.prompt)

    @property
    def total_len(self) -> int:
        return self.prompt_len + self.gen_len

    @property
    def frontend_len(self) -> int:
        """Modality-frontend prefix rows (0 for text-only archs)."""
        return 0 if self.frontend is None else len(self.frontend)

    @property
    def refresh_len(self) -> int:
        """Rows one Refresh materializes for this request: the frontend
        prefix (vlm/audio) plus the full text sequence. This is the
        request's segment length in the packed Refresh stream and its
        Refresh-phase scheduling cost."""
        return self.frontend_len + self.total_len

    @property
    def n_blocks(self) -> int:
        return self.gen_len // self.cfg.block_size

    @property
    def block_start(self) -> int:
        return self.prompt_len + self.block_idx * self.cfg.block_size

    # -- phase machine -------------------------------------------------------
    @property
    def phase(self) -> Phase:
        if self.step_in_block == 0:
            return Phase.REFRESH
        if self.cfg.refresh_interval and \
                self.step_in_block % self.cfg.refresh_interval == 0:
            return Phase.REFRESH
        return Phase.REUSE

    @property
    def query_tokens(self) -> int:
        """Scheduling currency (§4.4): frontend prefix + full seq in Refresh,
        block in Reuse (the active block is always text — frontend rows are
        never re-decoded, so Reuse and the logit stage cost no prefix)."""
        if self.phase == Phase.REFRESH:
            return self.refresh_len
        return self.cfg.block_size

    def refresh_key(self) -> bytes:
        """Content address of this request's next Refresh capture.

        The captured cache is a deterministic function of (tokens, geometry,
        frontend) under the engine's fixed params, so two requests with equal
        keys produce bit-identical pool rows — the dedup law KVPool's shared
        writes rely on (docs/memory.md)."""
        from repro.core.share_ledger import content_key
        return content_key(self.tokens, self.cfg.block_size, self.total_len,
                           self.block_start, self.frontend)

    def block_tokens(self) -> np.ndarray:
        s = self.block_start
        return self.tokens[s: s + self.cfg.block_size]

    def block_masked(self) -> int:
        return int((self.block_tokens() == self.mask_id).sum())

    def advance_control(self, n_commit: int, now: Optional[float]) -> int:
        """Advance the state machine by one committed denoising step WITHOUT
        the committed token values (they may still be in flight on device —
        the pipelined engine calls this at dispatch time and applies the
        synced values later via the recorded ``commit_epoch``).

        ``diffusion.commit_tokens`` unmasks exactly ``min(n_commit,
        masked)`` positions and never writes the mask id, so the masked
        count, block completion, and the FINISHED transition are all
        deterministic functions of ``n_commit`` and the counters here —
        value-independence is what makes dispatch-ahead bit-identical to
        the synchronous oracle. Returns the number of newly committed
        positions (the ``committed_tokens`` stat delta). ``now`` stamps
        ``t_first_commit`` / ``t_finished``; None leaves them to the caller
        (the wall-clock engine stamps them when the values land)."""
        n_act = min(n_commit, self.masked_left)
        if now is not None and self.t_first_commit < 0 and n_act > 0:
            self.t_first_commit = now
        self.masked_left -= n_act
        self.steps_done += 1
        self.step_in_block += 1
        done_block = self.masked_left == 0 or \
            self.step_in_block >= self.cfg.steps_per_block
        if done_block:
            self.block_idx += 1
            self.step_in_block = 0
            self.masked_left = self.cfg.block_size
            if self.block_idx >= self.n_blocks:
                self.state = State.FINISHED
                self.outcome = Outcome.FINISHED
                if now is not None:
                    self.t_finished = now
        return n_act

    def advance(self, new_block_tokens: np.ndarray, now: float) -> None:
        """Apply a committed denoising step and advance the state machine
        (the synchronous spelling: token values and control advance
        together — direct callers and the oracle tests use this)."""
        prev_masked = self.masked_left
        s = self.block_start
        self.tokens[s: s + self.cfg.block_size] = new_block_tokens
        n_left = int((new_block_tokens == self.mask_id).sum())
        self.advance_control(prev_masked - n_left, now)

    def rollback_block(self) -> int:
        """Preemption rollback: discard the active block's partial progress.

        The block region returns to all-mask and the step counter to 0, so
        on re-admission the phase machine's first step is a normal Refresh
        (step 0 of a block always refreshes) and the block's denoising
        trajectory — a deterministic function of the unchanged preceding
        context — replays bit-identically to the unpreempted run. Returns
        the number of discarded commits (recompute debt).

        The count comes from the CONTROL counter, not the token array: under
        the pipelined loop the latest commit's values may still be in
        flight, but ``masked_left`` already accounts for them, so the debt
        matches the synchronous oracle exactly. Bumping ``commit_epoch``
        makes the engine drop those in-flight values on sync instead of
        writing into the rolled-back block."""
        n = self.cfg.block_size - self.masked_left
        self.block_tokens()[:] = self.mask_id
        self.step_in_block = 0
        self.masked_left = self.cfg.block_size
        self.commit_epoch += 1
        self.recomputed_tokens += n
        return n

    def output_tokens(self) -> np.ndarray:
        return self.tokens[self.prompt_len: self.total_len]

    @property
    def latency(self) -> float:
        return self.t_finished - self.arrival

    @property
    def met_deadline(self) -> bool:
        """Finished and finished in time (goodput numerator)."""
        return self.state == State.FINISHED and self.t_finished <= self.deadline
