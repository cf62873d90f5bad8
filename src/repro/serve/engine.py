"""Serving engine: continuous batching over a fixed slot pool.

Requests queue in; free slots prefill (one request at a time here — the
multi-pod path shards prefill over the mesh) and then join the batched
decode step. Each decode step runs the whole slot pool through
``decode_step`` + the radix-CDF sampler; finished slots (EOS/max-len) are
recycled. KV caches live per-slot and are scatter-updated in the batch
dimension — the CPU-scale stand-in for paged attention.

Multi-tenant path: a request may carry its own static categorical
(``Request.prior`` — draft prior, per-client mixture, per-cell density).
Such requests bypass the model entirely: on admit the prior is inserted
into a :class:`~repro.serve.sampler.PooledForestSampler`'s size-class
arena, every step drains ALL prior-backed slots with one batched kernel
launch per touched size class, and retirement evicts the tenant (slot
handles are versioned, so churn can never sample a stale distribution).
With ``params=None`` the engine serves pure categorical traffic — the
paper's millions-of-users scenario with no LM in the loop.

2-D path: a request may instead carry ``Request.prior2d`` — an
environment/density map sampled as row-marginal x per-row conditional
(the paper's Sec. 5 application). All such requests share ONE
:class:`~repro.serve.sampler.SpatialSampler` (the map is a shared static
asset, like the model weights; per-request maps belong in the pool path as
flattened priors), every step drains ALL 2-D slots with one bulk
``sample_map`` call, and each emitted "token" is the flat texel id.
"""
from __future__ import annotations

import dataclasses
from collections import deque
from typing import Any

import numpy as np
import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig
from repro.robust.errors import RequestError, ServingError
from repro.robust.validate import classify_weights

from .sampler import PooledForestSampler, SpatialSampler, TokenSampler


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray
    max_new: int = 32
    eos: int | None = None
    prior: np.ndarray | None = None  # per-request categorical (pool path)
    # sampling method for the prior's pool slot: "forest" (monotone,
    # QMC-safe), "alias" (packed O(1) tables, bulk PRNG traffic), or
    # "auto" — let the prior sampler pick by its stream kind
    method: str = "auto"
    # 2-D map request: the engine's SHARED environment/density map (every
    # prior2d request must carry the same map; tokens are flat texel ids)
    prior2d: Any | None = None
    out: list[int] = dataclasses.field(default_factory=list)
    done: bool = False
    # set when the engine retires the request on a fault instead of
    # serving it (``on_fault="retire"``): "<code>: <detail>"
    error: str | None = None


class ServeEngine:
    """``on_fault`` picks the per-request failure semantics: ``"raise"``
    (default, the historical behavior — a malformed request surfaces as the
    structured exception from :meth:`submit`/:meth:`step`) or ``"retire"``
    — :meth:`step` isolates the failure to the offending request, retiring
    it with ``Request.error = "<code>: <detail>"`` while every other live
    slot keeps serving. Either way, malformed priors are caught with the
    :mod:`repro.robust.errors` taxonomy at :meth:`submit` time when the
    admission policy is strict, never as a mid-``step`` crash."""

    def __init__(self, params: Any, cfg: ModelConfig | None, n_slots: int = 8,
                 max_seq: int = 512, sampler: TokenSampler | None = None,
                 prior_sampler: PooledForestSampler | None = None,
                 spatial_sampler: SpatialSampler | None = None,
                 on_fault: str = "raise"):
        if on_fault not in ("raise", "retire"):
            raise ValueError(f"on_fault must be 'raise' or 'retire', got {on_fault!r}")
        self.params = params
        self.cfg = cfg
        self.n_slots = n_slots
        self.max_seq = max_seq
        self.on_fault = on_fault
        self.sampler = sampler or TokenSampler(n_slots=n_slots)
        self.prior_sampler = prior_sampler
        self.prior_handles: dict[int, Any] = {}  # slot -> pool Handle
        self.spatial_sampler = spatial_sampler
        self.spatial_slots: set[int] = set()  # slots on the 2-D map drain
        self.queue: deque[Request] = deque()
        self.slots: list[Request | None] = [None] * n_slots
        if params is not None:
            from repro.models import init_cache  # lazy: priors-only engines
                                                 # never touch the model layer
            self.cache = init_cache(cfg, n_slots, max_seq)
        else:
            self.cache = None
        self.pos = np.zeros(n_slots, np.int32)
        self.last_tok = np.zeros(n_slots, np.int32)
        self.steps = 0

    def _prior_policy(self) -> str:
        return self.prior_sampler.pool.policy if self.prior_sampler else "reject"

    def _validate(self, req: Request) -> None:
        """Submit-time structural validation: wrong dtype / negative
        entries / non-finite mass / shape mismatches are rejected HERE,
        with the structured taxonomy, not discovered as a mid-``step``
        exception. Weight-*value* violations defer to the prior pool's
        admission policy when it is lenient (clamp/quarantine repair at
        admit instead)."""
        if req.prior is not None:
            try:
                _, code = classify_weights(req.prior)
            except ServingError as e:
                raise RequestError(
                    f"request {req.rid}: prior {e.code}: {e}"
                ) from None
            if code is not None and self._prior_policy() == "reject":
                raise RequestError(f"request {req.rid}: prior {code}")
        if req.prior2d is not None:
            try:
                rows = [np.asarray(r, np.float64) for r in req.prior2d]
            except (TypeError, ValueError) as e:
                raise RequestError(
                    f"request {req.rid}: prior2d bad_dtype: {e}"
                ) from None
            if not rows or any(r.ndim != 1 or r.size == 0 for r in rows):
                raise RequestError(
                    f"request {req.rid}: prior2d bad_shape: want non-empty "
                    "1-D rows"
                )
            for r in rows:
                _, code = classify_weights(r, allow_zero_total=True)
                if code is not None:
                    raise RequestError(f"request {req.rid}: prior2d {code}")
            if self.spatial_sampler is not None:
                have = self.spatial_sampler.map.rows_raw
                if len(rows) != len(have) or any(
                    a.shape != b.shape for a, b in zip(rows, have)
                ):
                    raise RequestError(
                        f"request {req.rid}: prior2d map_mismatch: shape "
                        "differs from the engine's shared map"
                    )

    def submit(self, req: Request) -> None:
        if req.prior is not None and req.prior2d is not None:
            raise RequestError("a request carries prior OR prior2d, not both")
        if req.prior is None and req.prior2d is None and self.params is None:
            raise RequestError(
                "engine has no model (params=None); submit prior-backed "
                "requests only"
            )
        self._validate(req)
        self.queue.append(req)

    def _same_map(self, img) -> bool:
        rows = [np.asarray(r, np.float64) for r in img]
        have = self.spatial_sampler.map.rows_raw
        return len(rows) == len(have) and all(
            a.shape == b.shape and np.array_equal(a, b)
            for a, b in zip(rows, have)
        )

    def _fail_request(self, s: int, err: Exception) -> None:
        """Isolate one request's fault (``on_fault="retire"``): the request
        retires with a structured ``error`` result; the slot frees; every
        other live slot is untouched."""
        req = self.slots[s]
        if req is not None:
            req.error = f"{getattr(err, 'code', 'error')}: {err}"
            req.done = True
        self.slots[s] = None
        self.prior_handles.pop(s, None)
        self.spatial_slots.discard(s)

    def _admit_spatial(self, admitted: list[tuple[int, Request]]) -> None:
        """2-D admission wave: the engine's map is a shared static asset —
        the first ``prior2d`` request instantiates the
        :class:`SpatialSampler`; later requests must carry the identical
        map (a per-request map belongs in the pool path). The wave draws
        its first texels in one bulk ``sample_map`` drain."""
        if self.spatial_sampler is None:
            self.spatial_sampler = SpatialSampler(
                admitted[0][1].prior2d, n_slots=self.n_slots
            )
        kept = []
        for s, req in admitted:
            if not self._same_map(req.prior2d):
                err = RequestError(
                    f"request {req.rid}: prior2d differs from the engine's "
                    "shared map; per-request distributions go through "
                    "Request.prior (the pool path)"
                )
                if self.on_fault == "retire":
                    self._fail_request(s, err)
                    continue
                self.slots[s] = None
                raise err
            self.spatial_slots.add(s)
            kept.append((s, req))
        admitted = kept
        if not admitted:
            return
        slots = np.asarray([s for s, _ in admitted])
        toks = self.spatial_sampler.sample_flat(slots)
        for (s, req), tok in zip(admitted, toks):
            self.pos[s] = 0
            self.last_tok[s] = int(tok)
            req.out.append(int(tok))

    def _admit_priors(self, admitted: list[tuple[int, Request]]) -> None:
        """Prior-backed admission wave: no prefill, no KV — the whole wave
        joins the pool through the fused batched builder (one build launch
        per touched size class) and draws its first tokens in one batched
        drain."""
        if self.prior_sampler is None:
            self.prior_sampler = PooledForestSampler(n_slots=self.n_slots)
        try:
            hs = self.prior_sampler.add_many(
                [r.prior for _, r in admitted],
                method=[r.method for _, r in admitted],
            )
        except ValueError:
            if self.on_fault != "retire":
                for s, _ in admitted:
                    self.slots[s] = None
                raise
            # isolate: re-admit one by one, retiring only the bad tenants
            # (their co-tenants still get the same pool rows and samples)
            kept, hs = [], []
            for s, req in admitted:
                try:
                    hs.append(self.prior_sampler.add(req.prior,
                                                     method=req.method))
                    kept.append((s, req))
                except ValueError as e:
                    self._fail_request(s, e)
            admitted = kept
            if not admitted:
                return
        slots = np.asarray([s for s, _ in admitted])
        for (s, _), h in zip(admitted, hs):
            self.prior_handles[s] = h
        toks = self.prior_sampler.sample(hs, slots)
        for (s, req), tok in zip(admitted, toks):
            self.pos[s] = 0
            self.last_tok[s] = int(tok)
            req.out.append(int(tok))

    def _admit(self) -> None:
        priors: list[tuple[int, Request]] = []
        spatial: list[tuple[int, Request]] = []
        for s in range(self.n_slots):
            if self.slots[s] is None and self.queue:
                req = self.queue.popleft()
                self.slots[s] = req
                if req.prior is not None:
                    priors.append((s, req))
                    continue
                if req.prior2d is not None:
                    spatial.append((s, req))
                    continue
                from repro.models import prefill

                # prefill this request alone, then splice its cache into the
                # slot position of the batched cache
                batch = {"tokens": jnp.asarray(req.prompt[None, :], jnp.int32)}
                logits, cache1, _ = prefill(
                    self.params, self.cfg, batch, max_seq=self.max_seq
                )
                tok = self.sampler.sample(logits, np.array([s]))[0]

                def splice(big, one):
                    # leaves without a slot dim (e.g. stacked 'len' counters)
                    if one.ndim < 2 or big.shape[1] != self.n_slots:
                        return big
                    return big.at[:, s].set(one[:, 0])

                self.cache = jax.tree.map(splice, self.cache, cache1)
                self.pos[s] = len(req.prompt)
                self.last_tok[s] = tok
                req.out.append(int(tok))
        if priors:
            self._admit_priors(priors)
        if spatial:
            self._admit_spatial(spatial)

    def _retire(self) -> None:
        for s, req in enumerate(self.slots):
            if req is None:
                continue
            if (
                len(req.out) >= req.max_new
                or (req.eos is not None and req.out and req.out[-1] == req.eos)
                # max_seq is a KV budget; prior/2-D-backed slots hold no KV
                or (s not in self.prior_handles
                    and s not in self.spatial_slots
                    and self.pos[s] >= self.max_seq - 1)
            ):
                req.done = True
                self.slots[s] = None
                h = self.prior_handles.pop(s, None)
                if h is not None:
                    try:
                        self.prior_sampler.remove(h)
                    except ValueError:
                        # already evicted through an outside reference —
                        # the slot still frees either way
                        if self.on_fault != "retire":
                            raise
                # 2-D slots hold no pool handle — the map is shared; just
                # leave the drain set (slot streams keep their counters)
                self.spatial_slots.discard(s)

    def step(self) -> None:
        self._admit()
        active = [s for s, r in enumerate(self.slots) if r is not None]
        if not active:
            return
        model_slots = [
            s for s in active
            if s not in self.prior_handles and s not in self.spatial_slots
        ]
        prior_slots = [s for s in active if s in self.prior_handles]
        spatial_slots = [s for s in active if s in self.spatial_slots]
        if model_slots:
            from repro.models import decode_step

            # attention_decode scatters at per-row pos, so idle slots simply
            # overwrite their own stale cell; only active slots are read out.
            logits, new_cache = decode_step(
                self.params,
                self.cfg,
                self.cache,
                jnp.asarray(self.last_tok),
                jnp.asarray(self.pos),
            )
            self.cache = new_cache
            act = np.asarray(model_slots)
            toks = self.sampler.sample(logits[act], act)
            for i, s in enumerate(model_slots):
                tok = int(toks[i])
                self.slots[s].out.append(tok)
                self.last_tok[s] = tok
                self.pos[s] += 1
        if prior_slots and self.on_fault == "retire":
            # pre-drain screen: a slot whose pool handle went stale (e.g.
            # evicted through an outside pool reference) retires with a
            # structured error instead of poisoning the batched drain
            live = []
            for s in prior_slots:
                try:
                    self.prior_sampler.pool._check(self.prior_handles[s])
                    live.append(s)
                except ValueError as e:
                    self._fail_request(s, e)
            prior_slots = live
        if prior_slots:
            # the batched drain: every prior-backed slot, one stream-aware
            # pool call (device-side QMC counters, one launch per size class)
            hs = [self.prior_handles[s] for s in prior_slots]
            toks = self.prior_sampler.sample(hs, np.asarray(prior_slots))
            for i, s in enumerate(prior_slots):
                tok = int(toks[i])
                self.slots[s].out.append(tok)
                self.last_tok[s] = tok
                # pos stays frozen at 0: prior slots hold no KV, and pos
                # doubles as decode_step's scatter index for EVERY row — a
                # drifting pos would walk a prior slot's writes across (and
                # eventually past) the max_seq cache budget.
        if spatial_slots:
            # the 2-D bulk drain: every map-backed slot resolves its next
            # 2-D stream point through one sample_map call (marginal descent
            # + one conditional launch per touched size class); the emitted
            # token is the flat texel id. pos frozen at 0, as above.
            toks = self.spatial_sampler.sample_flat(np.asarray(spatial_slots))
            for i, s in enumerate(spatial_slots):
                tok = int(toks[i])
                self.slots[s].out.append(tok)
                self.last_tok[s] = tok
        self._retire()
        self.steps += 1

    def run(self, max_steps: int = 1000) -> None:
        while (self.queue or any(self.slots)) and self.steps < max_steps:
            self.step()

    # ---------------------------------------------------------- persistence

    @staticmethod
    def _req_state(r: Request | None):
        if r is None:
            return None
        return dict(
            rid=r.rid, prompt=np.asarray(r.prompt), max_new=r.max_new,
            eos=r.eos,
            prior=None if r.prior is None else np.asarray(r.prior, np.float64),
            method=r.method,
            prior2d=None if r.prior2d is None
            else [np.asarray(row, np.float64) for row in r.prior2d],
            out=list(r.out), done=r.done, error=r.error,
        )

    @staticmethod
    def _req_restore(d) -> Request | None:
        if d is None:
            return None
        return Request(
            rid=int(d["rid"]), prompt=np.asarray(d["prompt"]),
            max_new=int(d["max_new"]), eos=d["eos"],
            prior=None if d["prior"] is None else np.asarray(d["prior"]),
            method=d["method"],
            prior2d=None if d["prior2d"] is None
            else [np.asarray(row) for row in d["prior2d"]],
            out=[int(t) for t in d["out"]], done=bool(d["done"]),
            error=d["error"],
        )

    def snapshot(self) -> dict:
        """Full engine serving state: slot/queue requests, per-slot
        positions, pool handles, every sampler's exact stream state, and
        the KV cache leaves — everything except the model parameters
        themselves (pass those back to :meth:`restore`). Committed through
        :func:`repro.ckpt.save_state`, a killed process resumes with
        bit-identical subsequent drains."""
        cache_leaves = None
        if self.cache is not None:
            cache_leaves = [np.asarray(x)
                            for x in jax.tree_util.tree_leaves(self.cache)]
        return dict(
            kind="serve_engine",
            n_slots=self.n_slots, max_seq=self.max_seq,
            on_fault=self.on_fault,
            has_model=self.params is not None,
            steps=self.steps,
            pos=self.pos.copy(), last_tok=self.last_tok.copy(),
            queue=[self._req_state(r) for r in self.queue],
            slots=[self._req_state(r) for r in self.slots],
            prior_handles={int(s): tuple(h)
                           for s, h in self.prior_handles.items()},
            spatial_slots=set(self.spatial_slots),
            sampler=self.sampler.snapshot(),
            prior_sampler=None if self.prior_sampler is None
            else self.prior_sampler.snapshot(),
            spatial_sampler=None if self.spatial_sampler is None
            else self.spatial_sampler.snapshot(),
            cache=cache_leaves,
        )

    @classmethod
    def restore(cls, state: dict, params: Any = None,
                cfg: ModelConfig | None = None) -> "ServeEngine":
        """Rebuild an engine from :meth:`snapshot` output. A model-backed
        snapshot needs the (unsnapshotted) ``params``/``cfg`` passed back;
        pool handles stay valid because the pool snapshot carries its
        version counters."""
        if state.get("kind") != "serve_engine":
            raise ValueError(f"not a ServeEngine snapshot: {state.get('kind')!r}")
        if state["has_model"] and params is None:
            raise ValueError("snapshot was model-backed: pass params and cfg")
        eng = cls(params if state["has_model"] else None, cfg,
                  n_slots=int(state["n_slots"]), max_seq=int(state["max_seq"]),
                  on_fault=state.get("on_fault", "raise"))
        eng.steps = int(state["steps"])
        eng.pos = np.asarray(state["pos"], np.int32).copy()
        eng.last_tok = np.asarray(state["last_tok"], np.int32).copy()
        eng.queue = deque(cls._req_restore(d) for d in state["queue"])
        eng.slots = [cls._req_restore(d) for d in state["slots"]]
        from repro.pool import Handle  # lazy: keeps import edges thin

        eng.prior_handles = {
            int(s): Handle(int(h[0]), int(h[1]), int(h[2]), int(h[3]), h[4])
            for s, h in state["prior_handles"].items()
        }
        eng.spatial_slots = {int(s) for s in state["spatial_slots"]}
        eng.sampler = TokenSampler.restore(state["sampler"])
        if state["prior_sampler"] is not None:
            eng.prior_sampler = PooledForestSampler.restore(
                state["prior_sampler"]
            )
        if state["spatial_sampler"] is not None:
            eng.spatial_sampler = SpatialSampler.restore(
                state["spatial_sampler"]
            )
        if state["cache"] is not None and eng.cache is not None:
            treedef = jax.tree_util.tree_structure(eng.cache)
            eng.cache = jax.tree_util.tree_unflatten(
                treedef, [jnp.asarray(x) for x in state["cache"]]
            )
        return eng
