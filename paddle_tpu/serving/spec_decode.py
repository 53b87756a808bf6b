"""Draft-model speculative decoding over the serving engine (ISSUE 13,
docs/serving.md "Speculative decoding").

Decode is memory-bandwidth-bound: every step reads the whole weight set
and cache to emit ONE token per slot. Speculative decoding is the lever
that beats that physics — a small draft model proposes ``k`` tokens
autoregressively (cheap reads), then the target model scores the whole
window in ONE batched verify call (`DecodeEngine.verify_step`, the
``[B, W]`` executable) and accepts the longest prefix consistent with
its own distribution. Accepted tokens cost one target pass for up to
``k+1`` emissions.

Correctness contract (the acceptance bar tests hold this to):

- **Greedy (temperature=0)**: emitted tokens are EXACTLY what the target
  alone would emit — a draft token is accepted iff it equals the
  target's argmax at that position, the first mismatch is replaced by
  the target's own choice, and a fully-accepted window earns the bonus
  token from the last verify position.
- **Sampled**: standard rejection sampling (Leviathan et al. /
  arXiv:2211.17192): draft token ``d`` proposed from the draft's
  adjusted distribution ``p_d`` is accepted with probability
  ``min(1, p_t(d)/p_d(d))``; a rejection resamples from the residual
  ``norm(max(p_t - p_d, 0))`` — the emitted marginal is exactly the
  target's adjusted distribution. Both adjusted distributions come from
  ``sampling.adjusted_probs_np``, the numpy twin of the in-executable
  masking. Acceptance randomness derives from the request seed (host
  RNG, independent of the proposal keys) — deterministic replays.

Cache discipline: the verify window writes all ``W`` rows; only the
accepted prefix is committed (`commit_window`), rejected rows are simply
overwritten later. The draft keeps its own (smaller) cache in lockstep —
rolled back to the accepted length after every window, with a one-token
catch-up feed when a fully-accepted window leaves the draft one row
behind. Every shape is static, so speculative serving inherits the
zero-recompile steady state unchanged.

Acceptance telemetry: ``paddle_serve_spec_accepted_tokens`` (histogram
of accepted draft tokens per window) +
``paddle_serve_spec_{proposed_tokens,windows}_total`` — mean accepted
per window IS the speedup meter.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from . import metrics as smetrics
from . import sampling as samp
from .engine import DecodeEngine
from .sampling import GREEDY, SamplingParams

__all__ = ["SpecDecodeEngine", "SpecStats"]


@dataclasses.dataclass
class SpecStats:
    windows: int = 0
    proposed: int = 0
    accepted: int = 0
    emitted: int = 0

    @property
    def acceptance_rate(self) -> float:
        return self.accepted / self.proposed if self.proposed else 0.0

    @property
    def tokens_per_window(self) -> float:
        return self.emitted / self.windows if self.windows else 0.0


class SpecDecodeEngine:
    """Target + draft engine pair presenting the scheduler's engine
    surface (``start_sequence_sampled`` / ``generate_step`` /
    ``free_sequence`` / admission + capacity hooks), emitting up to
    ``k+1`` tokens per step.

    The target must be built with ``EngineConfig(verify_window=k+1)``;
    the draft is any (smaller) engine with the same vocab, slot count,
    max_seq and bucket ladder, so slot ids stay aligned across the two
    allocators by construction."""

    def __init__(self, target: DecodeEngine, draft: DecodeEngine):
        for eng in (target, draft):
            if eng.model.recurrent:
                raise ValueError(
                    f"{type(eng.model).__name__} has recurrent layers: the "
                    "speculative wrapper (SpecDecodeEngine) cannot carry "
                    "recurrent state (a rejected draft token cannot be "
                    "taken back out of it)")
        W = target.ecfg.verify_window
        if W < 2:
            raise ValueError(
                "target engine needs EngineConfig(verify_window=k+1>=2)")
        if draft.cfg.vocab_size != target.cfg.vocab_size:
            raise ValueError("draft/target vocab mismatch")
        for attr in ("max_batch", "max_seq"):
            if getattr(draft.ecfg, attr) != getattr(target.ecfg, attr):
                raise ValueError(f"draft/target {attr} mismatch")
        if draft.buckets != target.buckets:
            raise ValueError("draft/target bucket ladders differ "
                             "(slot alignment needs identical admission)")
        self.target = target
        self.draft = draft
        self.draft.meter_tokens = False      # draft tokens aren't served
        self.k = W - 1
        self.window = W
        # the scheduler evicts below this headroom: a verify window
        # writes W rows, so speculative requests stop within k tokens of
        # max_seq (max_new_tokens usually stops them far earlier)
        self.min_headroom = W
        self.stats = SpecStats()
        # tokens the target has cached that the draft hasn't ingested
        # yet (at most one — the fully-accepted window's last draft
        # token); fed to the draft at the head of the next proposal round
        self._pending: Dict[int, List[int]] = {}

    # -- facade ------------------------------------------------------------
    @property
    def cfg(self):
        return self.target.cfg

    @property
    def ecfg(self):
        return self.target.ecfg

    @property
    def cache(self):
        return self.target.cache

    @property
    def prefix(self):
        return self.target.prefix

    @property
    def buckets(self):
        return self.target.buckets

    @property
    def kv_path(self):
        return self.target.kv_path

    @property
    def held_shapes(self):
        return self.target.held_shapes

    def live_pages(self, slots) -> int:
        return self.target.live_pages(slots)

    def state_bytes(self, slots) -> int:
        return self.target.state_bytes(slots)

    @property
    def latent_token_bytes(self) -> int:
        return self.target.latent_token_bytes

    @property
    def poisoned(self):
        return self.target.poisoned or self.draft.poisoned

    @property
    def sched_step(self):
        return self.target.sched_step

    @sched_step.setter
    def sched_step(self, step):
        self.target.sched_step = self.draft.sched_step = step

    @property
    def compiles(self):
        return self.target.compiles + self.draft.compiles

    @property
    def steady_state_recompiles(self):
        return (self.target.steady_state_recompiles
                + self.draft.steady_state_recompiles)

    def warmup(self) -> Dict[str, float]:
        out = {f"target/{k}": v for k, v in self.target.warmup().items()}
        out.update({f"draft/{k}": v
                    for k, v in self.draft.warmup().items()})
        return out

    def bucket_for(self, n: int) -> int:
        return self.target.bucket_for(n)

    def can_admit(self, prompt_len: int) -> bool:
        return (self.target.can_admit(prompt_len)
                and self.draft.can_admit(prompt_len))

    def note_tokens(self, n: int) -> None:
        self.target.note_tokens(n)

    def reference_logits(self, tokens):
        return self.target.reference_logits(tokens)

    # -- lifecycle ---------------------------------------------------------
    def start_sequence(self, tokens: Sequence[int]):
        slot, logits, _tok = self.start_sequence_sampled(tokens, GREEDY)
        return slot, logits

    def start_sequence_sampled(self, tokens: Sequence[int],
                               params: SamplingParams):
        slot, logits, tok = self.target.start_sequence_sampled(
            tokens, params)
        try:
            d_slot, _d_logits, _d_tok = self.draft.start_sequence_sampled(
                tokens, GREEDY)
        except Exception:
            self.target.free_sequence(slot)
            raise
        if d_slot != slot:       # identical admission order -> identical
            self.draft.free_sequence(d_slot)
            self.target.free_sequence(slot)
            raise RuntimeError(
                f"draft slot {d_slot} != target slot {slot} — the two "
                "allocators fell out of lockstep")
        self._pending[slot] = []
        return slot, logits, tok

    def resume_sequence_sampled(self, tokens: Sequence[int],
                                params: SamplingParams):
        """Preemption resume (see DecodeEngine.resume_sequence_sampled):
        both engines replay the stream, keeping slots in lockstep."""
        slot, logits, tok = self.target.resume_sequence_sampled(
            tokens, params)
        try:
            d_slot, _dl, _dt = self.draft.resume_sequence_sampled(
                tokens, GREEDY)
        except Exception:
            self.target.free_sequence(slot)
            raise
        if d_slot != slot:
            self.draft.free_sequence(d_slot)
            self.target.free_sequence(slot)
            raise RuntimeError(
                f"draft slot {d_slot} != target slot {slot} on resume")
        self._pending[slot] = []
        return slot, logits, tok

    def free_sequence(self, slot: int) -> None:
        self.target.free_sequence(slot)
        self.draft.free_sequence(slot)
        self._pending.pop(slot, None)

    def ensure_decode_capacity(self, slot: int, extra: int = 0) -> bool:
        extra = extra or self.window
        return (self.target.ensure_decode_capacity(slot, extra=extra)
                and self.draft.ensure_decode_capacity(slot, extra=extra))

    # -- the speculative step ---------------------------------------------
    def _accept_greedy(self, proposals: List[int],
                       target_toks: np.ndarray) -> Tuple[int, List[int]]:
        """Longest matching prefix; emitted = accepted + target's fix-up
        (which is the bonus token when everything matched)."""
        m = 0
        while m < len(proposals) and proposals[m] == int(target_toks[m]):
            m += 1
        return m, proposals[:m] + [int(target_toks[m])]

    def _accept_sampled(self, slot: int, start: int,
                        proposals: List[int],
                        draft_logits: List[np.ndarray],
                        target_logits: np.ndarray,
                        target_toks: np.ndarray,
                        sp: SamplingParams) -> Tuple[int, List[int]]:
        """Leviathan rejection sampling against the adjusted
        distributions. ``target_logits`` is [W, V]; row i is conditioned
        on the window up to (and including) proposal i-1."""
        rng = np.random.RandomState(
            (int(np.uint32(sp.seed)) * 2654435761
             + int(start) * 40503 + int(slot)) % 0x7FFFFFFF)
        emitted: List[int] = []
        m = 0
        for i, d in enumerate(proposals):
            pt = samp.adjusted_probs_np(target_logits[i], sp)
            pd = samp.adjusted_probs_np(draft_logits[i], sp)
            if pd[d] <= 0:           # defensive: proposal off-support
                ratio = 0.0
            else:
                ratio = min(1.0, float(pt[d] / pd[d]))
            if rng.uniform() < ratio:
                emitted.append(int(d))
                m += 1
                continue
            residual = np.maximum(pt - pd, 0.0)
            tot = residual.sum()
            if tot <= 0:             # pt == pd exactly: keep pt's sample
                emitted.append(int(np.argmax(pt)))
            else:
                emitted.append(int(rng.choice(len(residual),
                                              p=residual / tot)))
            return m, emitted
        # fully accepted: the bonus token is the executable's own sample
        # at the last window position (conditioned on every proposal)
        emitted.append(int(target_toks[len(proposals)]))
        return m, emitted

    def generate_step(
            self, slot_tokens: Dict[int, int],
            params_by_slot: Optional[Dict[int, SamplingParams]] = None
    ) -> Dict[int, List[int]]:
        """One speculative step for {slot: last emitted token} ->
        {slot: emitted tokens} (1..k+1 per slot)."""
        if not slot_tokens:
            return {}
        params_by_slot = params_by_slot or {}
        k = self.k
        # 1. draft catch-up: feed tokens the target cached last round
        pending = {s: list(self._pending.get(s, ()))
                   for s in slot_tokens}
        while any(pending.values()):
            round_feed = {s: toks.pop(0)
                          for s, toks in pending.items() if toks}
            self.draft.decode_step_sampled(round_feed, None)
        for s in slot_tokens:
            self._pending[s] = []
        # 2. draft proposes k tokens (sampled from ITS adjusted
        # distribution under the request's knobs — the proposal
        # distribution the rejection test assumes)
        proposals: Dict[int, List[int]] = {s: [] for s in slot_tokens}
        draft_logits: Dict[int, List[np.ndarray]] = {
            s: [] for s in slot_tokens}
        feed = dict(slot_tokens)
        for _ in range(k):
            out = self.draft.decode_step_sampled(feed, params_by_slot)
            feed = {}
            for s, (tok, logits) in out.items():
                proposals[s].append(int(tok))
                draft_logits[s].append(logits)
                feed[s] = int(tok)
        # 3. ONE batched target verify over [t_last, d_1..d_k]
        windows = {s: [slot_tokens[s]] + proposals[s]
                   for s in slot_tokens}
        starts = {s: self.target.cache.length(s) for s in slot_tokens}
        vout = self.target.verify_step(windows, params_by_slot)
        # 4. host-side acceptance
        result: Dict[int, List[int]] = {}
        total_emitted = 0
        for s, (t_logits, t_toks) in vout.items():
            sp = params_by_slot.get(s, GREEDY)
            if sp.greedy:
                m, emitted = self._accept_greedy(proposals[s], t_toks)
            else:
                m, emitted = self._accept_sampled(
                    s, starts[s], proposals[s], draft_logits[s],
                    t_logits, t_toks, sp)
            # target: rows start..start+m hold [t_last, d_1..d_m] — all
            # emitted-but-last tokens plus the window input
            self.target.commit_window(s, m + 1)
            # draft: proposal steps advanced it to start+k; roll back to
            # the accepted length (rows start..start+m are valid there
            # too for m < k; a fully-accepted window leaves d_k pending)
            if m < k:
                self.draft.cache.set_length(s, starts[s] + m + 1)
            else:
                self.draft.cache.set_length(s, starts[s] + k)
                self._pending[s] = [proposals[s][-1]]
            smetrics.m_spec_windows.inc()
            smetrics.m_spec_proposed.inc(k)
            smetrics.m_spec_accepted.observe(m)
            self.stats.windows += 1
            self.stats.proposed += k
            self.stats.accepted += m
            self.stats.emitted += len(emitted)
            total_emitted += len(emitted)
            result[s] = emitted
        self.note_tokens(total_emitted)
        return result
