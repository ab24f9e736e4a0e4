"""Split execution of a planned partition: edge prefix / cloud suffix.

``PartitionExecutor`` slices a ``Model``'s stacked per-unit parameters at a
layer boundary and runs the two halves as they would deploy:

  * the EDGE side owns the stem (embedding + modality projector) and the
    first ``cut_layer`` transformer layers; its prefill emits the cut
    activations that would ship over the channel;
  * the CLOUD side owns the remaining layers, the final norm, and the LM
    head; it finishes prefill and drives the action-chunk decode.

Decode ping-pongs per token (the suffix owner samples, the prefix owner
embeds), exactly the round-trip the planner prices.  Both phases run the
same ``Model._block_seq`` / ``Model._block_step`` kernels as the fused
single-device path, so the split forward is numerically identical to the
unpartitioned model — the property ``tests/test_partition.py`` pins.

``PartitionedPolicy`` is a drop-in ``CloudPolicy``: same observation-in /
action-chunk-out interface, plus modeled channel telemetry per call.

For fleet serving the executor additionally exposes a *batched cloud-suffix*
mode (``edge_prefill`` / ``suffix_prefill`` / ``build_fleet_decode``):
per-robot edge prefixes feed one ragged batch of cut activations into a
paged suffix that shares the continuous-batching scheduler's KV page pool —
see ``runtime/scheduler.py``'s split lane.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.data.pipeline import EpisodeTokenizer
from repro.launch.sharding import shard
from repro.models.layers import embed_lookup, rms_norm
from repro.models.model import Model
from repro.models.moe import moe_apply_experts
from repro.obs.clock import clock
from repro.partition.planner import TOKEN_ID_BYTES, interior_net_ms
from repro.runtime.channel import ChannelConfig, roundtrip_ms
from repro.runtime.kv_cache import donating_jit, scatter_prompt_into_pool


class PartitionExecutor:
    """Run ``model`` split after ``cut_layer`` transformer layers.

    Heterogeneous fleets run several cuts concurrently: ``with_cut`` derives
    a sibling executor at a different boundary that SHARES the per-layer
    parameter slices (jax arrays are immutable, the edge/cloud tuples are
    views), so a frontier of k cuts costs one slicing pass plus k cheap
    boundary re-partitions — not k copies of the model.

    ``expert_offload`` lists edge-side MoE layer indices whose EXPERT FFNs
    live cloud-side (the planner's second placement axis): the edge runs
    the layer's attention + router, ships the top-k-selected hidden states
    cloudward, the cloud applies the resident expert FFNs
    (``moe_apply_experts`` — the literal scan the fused model runs) and
    ships the mixture output back.  The robot-side prompt prefill
    (``edge_prefill``) realizes the hop as separate edge / cloud programs
    chained through the host; the fused decode window keeps the seam
    structural (same ops, one program) and prices the legs via
    ``modeled_net_ms`` / ``record_chunk_bytes``, like the cut itself.
    """

    def __init__(
        self,
        model: Model,
        params,
        cut_layer: int,
        channel: Optional[ChannelConfig] = None,
        expert_offload: Tuple[int, ...] = (),
        _shared: Optional[Tuple[tuple, Dict[str, Any]]] = None,
    ):
        cfg = model.cfg
        if cfg.encoder_decoder:
            raise NotImplementedError("split execution targets decoder-only stacks")
        if not 0 <= cut_layer <= cfg.num_layers:
            raise ValueError(f"cut_layer {cut_layer} outside [0, {cfg.num_layers}]")
        self.model = model
        self.cfg = cfg
        self.cut_layer = cut_layer
        self.channel = channel or ChannelConfig()
        self.expert_offload = tuple(sorted({int(l) for l in expert_offload}))
        self._offload_set = frozenset(self.expert_offload)
        for l in self.expert_offload:
            if not 0 <= l < cut_layer:
                raise ValueError(
                    f"expert_offload layer {l} not edge-side of cut {cut_layer}"
                )
            if not (model.specs[l][1] and cfg.d_ff > 0 and cfg.moe is not None):
                raise ValueError(f"expert_offload layer {l} is not an MoE layer")
        if self.expert_offload and model.moe_impl != "dense":
            raise ValueError(
                "gather/scatter expert offload splits the dense MoE path; "
                "capacity dispatch keeps experts fused"
            )
        self.shipped_bytes = 0.0
        # optional Observability handle (attach_partition sets it): when
        # present, the edge and suffix prefill legs record per-cut dispatch
        # times
        self.obs = None
        self._gs_fns: Dict[Any, Any] = {}  # host-composed gather/scatter jits

        if _shared is None:
            # per-layer params with the stacked repeats dim sliced out
            per_layer = []
            for i in range(cfg.num_layers):
                j, r = i % model.period, i // model.period
                per_layer.append(jax.tree.map(lambda a: a[r], params["unit"][j]))
            base: Dict[str, Any] = {
                "embed": params["embed"],
                "final_norm": params["final_norm"],
            }
            if "mod_proj" in params:
                base["mod_proj"] = params["mod_proj"]
            if "lm_head" in params:
                base["lm_head"] = params["lm_head"]
            _shared = (tuple(per_layer), base)
        self._per_layer, self._base = _shared
        sp: Dict[str, Any] = dict(self._base)
        sp["edge"] = self._per_layer[:cut_layer]
        sp["cloud"] = self._per_layer[cut_layer:]
        self.split_params = sp
        self.edge_specs = model.specs[:cut_layer]
        self.cloud_specs = model.specs[cut_layer:]

    def with_cut(
        self, cut_layer: int, expert_offload: Tuple[int, ...] = ()
    ) -> "PartitionExecutor":
        """A sibling executor at ``cut_layer`` sharing the sliced params.

        ``expert_offload`` does NOT inherit: a sibling is a fresh lane, and
        an offload set valid under one cut may be out of range under
        another — pass it explicitly to derive an expert-offload lane.
        """

        expert_offload = tuple(sorted({int(l) for l in expert_offload}))
        if cut_layer == self.cut_layer and expert_offload == self.expert_offload:
            return self
        sibling = PartitionExecutor(
            self.model, None, cut_layer, self.channel, expert_offload,
            _shared=(self._per_layer, self._base),
        )
        sibling.obs = self.obs
        return sibling

    @property
    def lane_key(self):
        """Scheduler lane-registry key: the plain cut for pure layer cuts
        (backwards compatible with cut-keyed callers), ``(cut, offload)``
        for expert-offload lanes — two lanes may then share a cut boundary
        while keeping distinct channel pricing and telemetry."""

        if self.expert_offload:
            return (self.cut_layer, self.expert_offload)
        return self.cut_layer

    # ------------------------------------------------------------------
    # full-sequence split forward (the parity surface)
    # ------------------------------------------------------------------

    def _run_side(self, specs, layer_params, x, positions):
        dummy = {"_": jnp.zeros((), jnp.float32)}
        for spec, p in zip(specs, layer_params):
            x, _, _ = self.model._block_seq(spec, p, x, positions, dummy)
        return x

    # -- gather/scatter seam: edge blocks with offloaded expert FFNs -------
    #
    # An offloaded MoE layer runs as mixer -> (norm2 + router) -> expert
    # scan -> residual, which recomposes the dense ``moe_forward`` op-for-op
    # (see ``Model._moe_pre_dispatch``): the split numbers equal the fused
    # numbers bit-for-bit, the parity the gather/scatter tests pin.

    def _edge_seq_blocks(self, sp, x, positions, caches):
        """Edge prefix, full-sequence mode -> (x, new caches)."""

        new = []
        for j, (spec, p, c) in enumerate(zip(self.edge_specs, sp["edge"], caches)):
            if j in self._offload_set:
                x, nc = self.model._block_mix_seq(spec, p, x, positions, c)
                h2, combine = self.model._moe_pre_dispatch(p, x)
                x = x + moe_apply_experts(h2, combine, p["moe"], self.cfg)
            else:
                x, nc, _ = self.model._block_seq(spec, p, x, positions, c)
            new.append(nc)
        return x, new

    def _edge_step_blocks(self, sp, x, caches, length):
        """Edge prefix, single-token decode mode -> (x, new caches)."""

        new = []
        for j, (spec, p, c) in enumerate(zip(self.edge_specs, sp["edge"], caches)):
            if j in self._offload_set:
                x, nc = self.model._block_mix_step(spec, p, x, c, length)
                h2, combine = self.model._moe_pre_dispatch(p, x)
                x = x + moe_apply_experts(h2, combine, p["moe"], self.cfg)
            else:
                x, nc = self.model._block_step(spec, p, x, c, length)
            new.append(nc)
        return x, new

    def edge_forward(self, batch) -> Tuple[jax.Array, jax.Array]:
        """Stem + edge prefix -> (cut activations [B,S,D], positions)."""

        x = self.model._embed_inputs(self.split_params, batch)
        positions = jnp.arange(x.shape[1])[None, :]
        dummy = [{"_": jnp.zeros((), jnp.float32)}] * len(self.edge_specs)
        x, _ = self._edge_seq_blocks(self.split_params, x, positions, dummy)
        return x, positions

    def cloud_forward(self, x, positions) -> jax.Array:
        """Cloud suffix + final norm -> hidden [B,S,D]."""

        x = self._run_side(self.cloud_specs, self.split_params["cloud"], x, positions)
        return rms_norm(x, self.split_params["final_norm"], self.cfg.norm_eps)

    def forward(self, batch) -> jax.Array:
        """End-to-end split forward; equals ``Model.forward``'s hidden."""

        x, positions = self.edge_forward(batch)
        self.shipped_bytes += float(np.prod(x.shape)) * x.dtype.itemsize
        return self.cloud_forward(x, positions)

    def logits(self, x) -> jax.Array:
        return self.model._logits(self.split_params, x)

    # ------------------------------------------------------------------
    # split serving path (prefill + fused ping-pong decode)
    # ------------------------------------------------------------------

    def _init_side_caches(self, specs, batch: int, seq: int):
        caches = []
        for spec in specs:
            c = self.model._init_block_cache(spec, batch, seq)
            caches.append(jax.tree.map(lambda a: a[0], c))
        return caches

    def split_prefill(self, sp, batch, extra: int):
        """Both halves prefill their own caches -> (logits [B,1,V], state)."""

        b = batch["tokens"].shape[0]
        s = self.model._total_seq(batch)
        x = self.model._embed_inputs(sp, batch)
        positions = jnp.arange(x.shape[1])[None, :]

        def run(specs, layer_params, caches, x):
            new = []
            for spec, p, c in zip(specs, layer_params, caches):
                x, nc, _ = self.model._block_seq(spec, p, x, positions, c)
                new.append(nc)
            return x, new

        edge_caches = self._init_side_caches(self.edge_specs, b, s + extra)
        cloud_caches = self._init_side_caches(self.cloud_specs, b, s + extra)
        x, edge_caches = self._edge_seq_blocks(sp, x, positions, edge_caches)
        x, cloud_caches = run(self.cloud_specs, sp["cloud"], cloud_caches, x)
        x = rms_norm(x, sp["final_norm"], self.cfg.norm_eps)
        logits = self.model._logits(sp, x[:, -1:])
        state = {
            "edge": edge_caches,
            "cloud": cloud_caches,
            "len": jnp.asarray(s, jnp.int32),
        }
        return logits, state

    def split_decode_step(self, sp, token, state):
        """One ping-pong: edge embeds+runs prefix, cloud finishes + samples."""

        cfg = self.cfg
        x = embed_lookup(token, sp["embed"], cfg.d_model, cfg.scale_embeddings)
        x = x.astype(self.model.dtype)

        def run(specs, layer_params, caches, x):
            new = []
            for spec, p, c in zip(specs, layer_params, caches):
                x, nc = self.model._block_step(spec, p, x, c, state["len"])
                new.append(nc)
            return x, new

        x, edge_caches = self._edge_step_blocks(sp, x, state["edge"], state["len"])
        x, cloud_caches = run(self.cloud_specs, sp["cloud"], state["cloud"], x)
        x = rms_norm(x, sp["final_norm"], cfg.norm_eps)
        logits = self.model._logits(sp, x)
        new_state = {
            "edge": edge_caches,
            "cloud": cloud_caches,
            "len": state["len"] + 1,
        }
        return logits, new_state

    def split_decode_chunk(self, sp, logits, state, n_steps: int, token_floor: int = 0):
        """Fused greedy split decode (mirrors ``Model.decode_chunk``)."""

        def step(carry, _):
            logits, st = carry
            ls = logits[:, -1]
            if token_floor:
                ls = ls.at[..., :token_floor].set(-1e9)
            tok = jnp.argmax(ls, axis=-1)[:, None]
            logits, st = self.split_decode_step(sp, tok, st)
            return (logits, st), tok[:, 0]

        (logits, state), toks = jax.lax.scan(
            step, (logits, state), None, length=n_steps
        )
        return jnp.moveaxis(toks, 0, 1), logits, state

    # ------------------------------------------------------------------
    # batched cloud-suffix serving (the scheduler's split lane)
    # ------------------------------------------------------------------
    #
    # ``serve_fleet`` runs many partitioned robots against one cloud: each
    # robot's edge prefix stays a private batch-1 dense-cache stack (it IS
    # the robot's device), while the cloud suffix serves *all* of them as
    # one ragged batch over the shared KV page pool — the same paged decode
    # substrate (``Model._block_step`` paged mode) the cloud-only engine
    # uses, drawing pages from the same allocator.

    def build_suffix_fns(self, spec, extra: int) -> None:
        """Compile edge/suffix entry points (``spec``: pool ``PagedSpec``)."""

        self._suffix_spec = spec
        self._edge_extra = extra
        self._edge_prefill_j = jax.jit(self._edge_prefill_impl)
        self._suffix_prefill_j = jax.jit(self._suffix_prefill_impl)

    def init_layer_pool(self, spec):
        """One attention layer's suffix K/V page pools (+1 trash page each).

        K and V are DISTINCT zero buffers: the fused fleet decode donates
        the pool pytree, and two leaves aliasing one buffer cannot both be
        donated.  Pools are owned by the scheduler and keyed by MODEL layer
        index, so every lane whose cut precedes a layer shares that layer's
        physical pool (page ids are globally unique — one allocator).
        """

        cfg = self.cfg
        hd, nkv = cfg.resolved_head_dim, cfg.num_kv_heads
        shape = (spec.num_pages + 1, spec.page_size, nkv, hd)
        # sharded serving: suffix pools shard over the global page dim too,
        # so split-lane suffix KV lands on the shard that owns its pages
        return {
            "kp": shard(jnp.zeros(shape, self.model.dtype),
                        "pages", None, None, None),
            "vp": shard(jnp.zeros(shape, self.model.dtype),
                        "pages", None, None, None),
        }

    def init_lane_state(self, spec, rows: int):
        """Per-row recurrent (non-attention) cloud-suffix state, keyed by
        MODEL layer index — unlike the shared attention pools, this state is
        per lane (each cut decodes its own rows through the tail)."""

        out = {}
        for j, s in enumerate(self.cloud_specs):
            if s[0] != "attn":
                c = self.model._init_block_cache(s, rows, spec.tokens_per_seq)
                out[self.cut_layer + j] = jax.tree.map(lambda a: a[0], c)
        return out

    def pad_lane_state(self, state, pad: int):
        """Grow the per-row recurrent state by ``pad`` rows."""

        return {
            layer: jax.tree.map(
                lambda a: jnp.concatenate(
                    [a, jnp.zeros((pad,) + a.shape[1:], a.dtype)], 0
                ),
                st,
            )
            for layer, st in state.items()
        }

    def init_edge_rows(self, rows: int, seq_len: int):
        """Row-batched dense edge-prefix caches for the pipelined lane.

        The per-robot batch-1 edge caches (the robots' devices) are merged
        into rows of these arrays at admission, so a whole window of edge
        steps can run device-resident inside the fused fleet decode.
        """

        return self._init_side_caches(self.edge_specs, rows, seq_len)

    def pad_edge_rows(self, caches, pad: int):
        """Grow the row-batched edge caches by ``pad`` rows."""

        return [
            jax.tree.map(
                lambda a: jnp.concatenate(
                    [a, jnp.zeros((pad,) + a.shape[1:], a.dtype)], 0
                ),
                c,
            )
            for c in caches
        ]

    def merge_edge_rows(self, edge_rows, new_caches, row_idx):
        """Install batch-1 robot edge caches as rows of the lane's arrays.

        Full-row overwrite, so a recycled row carries no stale KV or
        recurrent state from its previous occupant (idle rows accumulate
        clamped garbage writes inside fused windows by design).
        """

        for caches, ri in zip(new_caches, row_idx):
            edge_rows = [
                jax.tree.map(
                    lambda live, st: live.at[ri].set(
                        st[0].astype(live.dtype), mode="drop"
                    ),
                    lv, st,
                )
                for lv, st in zip(edge_rows, caches)
            ]
        return edge_rows

    def _stamp(self, side: str, op: str, t0: float) -> None:
        """Record one host-leg dispatch duration into the lane's histogram
        (``lane.edge_ms`` / ``lane.suffix_ms`` labeled by cut + op).  The
        call is async-dispatch timing — no device sync is added."""

        self.obs.metrics.histogram(
            f"lane.{side}_ms", cut=self.cut_layer, op=op
        ).observe((clock() - t0) * 1e3)

    def edge_prefill(self, tokens: np.ndarray):
        """Robot-side prompt prefill -> (cut activations [1,S,D], edge caches)."""

        run = (
            self._gs_edge_prefill
            if self.expert_offload
            else lambda t: self._edge_prefill_j(self.split_params, jnp.asarray(t))
        )
        if self.obs is None:
            return run(tokens)
        t0 = clock()
        out = run(tokens)
        self._stamp("edge", "prefill", t0)
        return out

    def _edge_prefill_impl(self, sp, tokens):
        batch = {"tokens": tokens}
        x = self.model._embed_inputs(sp, batch)
        positions = jnp.arange(x.shape[1])[None, :]
        caches = self._init_side_caches(
            self.edge_specs, tokens.shape[0], x.shape[1] + self._edge_extra
        )
        return self._edge_seq_blocks(sp, x, positions, caches)

    # ------------------------------------------------------------------
    # host-composed gather/scatter legs (robot-side prompt prefill)
    # ------------------------------------------------------------------
    #
    # With experts offloaded, the robot-side prompt prefill runs as separate
    # edge / cloud PROGRAMS chained through the host — the deployment shape
    # the planner prices: one edge segment per stretch of resident layers,
    # the cloud expert program (``moe_apply_experts``) between them.  The
    # whole-edge jit above stays the single-program reference.

    def _gs_jit(self, key, make):
        fn = self._gs_fns.get(key)
        if fn is None:
            fn = jax.jit(make())
            self._gs_fns[key] = fn
        return fn

    def _gs_block_calls(self, sp, x, caches, positions):
        """Run the edge prefix (full-sequence mode) as per-layer
        host-dispatched programs.

        Offloaded layers hop: edge mixer+router program -> cloud expert
        program -> edge residual add, three dispatches with the shipped
        tensors ((h2, combine) up, the mixture output down) crossing the
        host exactly where the channel would sit.
        """

        new = []
        for j, (spec, p, c) in enumerate(zip(self.edge_specs, sp["edge"], caches)):
            if j in self._offload_set:
                mix = self._gs_jit(("mix_seq", j), lambda spec=spec: (
                    lambda p, x, pos, c: self.model._block_mix_seq(spec, p, x, pos, c)
                ))
                x, nc = mix(p, x, positions, c)
                pre = self._gs_jit("pre_dispatch", lambda: self.model._moe_pre_dispatch)
                h2, combine = pre(p, x)
                # >>> uplink: top-k-selected hidden states + combine weights
                experts = self._gs_jit("experts", lambda: (
                    lambda moe_p, h2, cmb: moe_apply_experts(h2, cmb, moe_p, self.cfg)
                ))
                out2 = experts(p["moe"], h2, combine)
                # <<< downlink: expert-mixture output
                add = self._gs_jit("residual", lambda: (lambda a, b: a + b))
                x = add(x, out2)
            else:
                blk = self._gs_jit(("blk_seq", j), lambda spec=spec: (
                    lambda p, x, pos, c: self.model._block_seq(spec, p, x, pos, c)[:2]
                ))
                x, nc = blk(p, x, positions, c)
            new.append(nc)
        return x, new

    def _gs_edge_prefill(self, tokens):
        sp = self.split_params
        tokens = jnp.asarray(tokens)
        emb = self._gs_jit("embed", lambda: (
            lambda sp, t: self.model._embed_inputs(sp, {"tokens": t})
        ))
        x = emb(sp, tokens)
        positions = jnp.arange(x.shape[1])[None, :]
        caches = self._init_side_caches(
            self.edge_specs, tokens.shape[0], x.shape[1] + self._edge_extra
        )
        return self._gs_block_calls(sp, x, caches, positions)

    def suffix_prefill(self, x, layers, pt_new, row_idx, lens, caps):
        """Cloud-side prefill over a batch of shipped cut activations.

        Scatters each new sequence's suffix KV into its allocated pages and
        merges recurrent state at the claimed rows.  Returns
        (new layers, last-token logits [n, V]).
        """

        if self.obs is None:
            return self._suffix_prefill_j(
                self.split_params, jnp.asarray(x), layers, jnp.asarray(pt_new),
                jnp.asarray(row_idx), jnp.asarray(lens), jnp.asarray(caps),
            )
        t0 = clock()
        out = self._suffix_prefill_j(
            self.split_params, jnp.asarray(x), layers, jnp.asarray(pt_new),
            jnp.asarray(row_idx), jnp.asarray(lens), jnp.asarray(caps),
        )
        self._stamp("suffix", "prefill", t0)
        return out

    def _suffix_prefill_impl(self, sp, x, layers, pt_new, row_idx, lens, caps):
        n, s = x.shape[0], x.shape[1]
        positions = jnp.arange(s)[None, :]
        caches = self._init_side_caches(self.cloud_specs, n, s)
        x = x.astype(self.model.dtype)
        new_layers = []
        for spec, p, c, pool in zip(self.cloud_specs, sp["cloud"], caches, layers):
            x, nc, _ = self.model._block_seq(spec, p, x, positions, c)
            if spec[0] == "attn":
                new_layers.append({
                    "kp": scatter_prompt_into_pool(pool["kp"], nc["k"], pt_new, lens),
                    "vp": scatter_prompt_into_pool(pool["vp"], nc["v"], pt_new, lens),
                })
            else:
                new_layers.append(jax.tree.map(
                    lambda live, st: live.at[row_idx].set(
                        st.astype(live.dtype), mode="drop"
                    ),
                    pool, nc,
                ))
        x = rms_norm(x, sp["final_norm"], self.cfg.norm_eps)
        logits = self.model._logits(sp, x[:, -1:])
        return new_layers, logits[:, -1]

    # ------------------------------------------------------------------
    # pipelined fleet decode (device-resident split windows)
    # ------------------------------------------------------------------

    def build_fleet_decode(self, cuts: Tuple[int, ...], n_steps: int,
                           token_floor: int,
                           offloads: Optional[Tuple[Tuple[int, ...], ...]] = None):
        """One jitted window of pipelined split decode over a fleet of lanes.

        ``cuts`` lists the active lanes' cut layers, ascending (duplicates
        allowed: a plain layer-cut lane and an expert-offload lane may share
        a boundary — both join the suffix batch at the same layer);
        ``offloads`` optionally gives each lane's offloaded-expert layer
        set, whose blocks run through the gather/scatter seam (mixer →
        router → ``moe_apply_experts`` → residual; the same ops the fused
        block traces, so mixed lanes decode bit-identically — placement
        changes modeled channel cost and telemetry, not tokens);
        the returned fn runs ``n_steps`` (argmax → edge prefix → cloud
        suffix) iterations in a single ``lax.scan`` with no host sync —
        the executor-side realization of the planner's pipelined pricing:
        instead of a per-token host ping-pong (sample on host,
        ship token, edge step, ship activation, suffix step), every leg is
        one fused device program, so edge compute of token t+1 overlaps
        the suffix of token t under XLA's scheduler and the channel hops
        vanish from the critical path.

        Heterogeneous cuts batch their *compatible suffixes*: lanes join a
        progressively concatenated row batch at their cut layer, so each
        shared tail layer runs ONCE over the combined rows.  Attention
        layers read/write the caller's shared per-model-layer page pools
        (concatenated page tables index one physical pool — page ids are
        globally unique); recurrent layers concatenate the joined lanes'
        per-row state and slice it back.

        Signature of the returned fn::

            fn(per_layer, base, pools, lanes, pts, caps)
              -> (toks, new_lanes, new_pools)

        ``pools``: {model layer idx: {"kp","vp"}} for attn layers >= the
        shallowest cut.  ``lanes``: per-lane dicts with f32 ``logits``
        [R_i, V], row-batched ``edge`` caches, ``state`` ({layer: per-row
        recurrent state}), int32 ``lens`` [R_i].  ``pts``/``caps``: per-lane
        page tables / capacities.  ``pools`` and ``lanes`` are DONATED —
        the caller must rebind both to the outputs.  ``toks`` is a per-lane
        tuple of [R_i, n_steps] int arrays; logits come back f32 (lossless
        round-trip for f32/bf16 models, so windows chain bit-identically
        with the serial path's host-side argmax).
        """

        model, cfg = self.model, self.cfg
        specs = model.specs
        num_layers = cfg.num_layers
        first = cuts[0]
        n_lanes = len(cuts)
        off_sets = tuple(
            frozenset(offloads[li]) if offloads else frozenset()
            for li in range(n_lanes)
        ) if offloads else (frozenset(),) * n_lanes

        def fleet(per_layer, base, pools, lanes, pts, caps):
            def body(carry, _):
                lanes_c, pools_c = carry
                xs, toks_out, edges_new = [], [], []
                for li in range(n_lanes):
                    lane = lanes_c[li]
                    ls = lane["logits"]
                    if token_floor:
                        ls = ls.at[:, :token_floor].set(-1e9)
                    tok = jnp.argmax(ls, axis=-1)
                    toks_out.append(tok)
                    x = embed_lookup(
                        tok[:, None], base["embed"], cfg.d_model,
                        cfg.scale_embeddings,
                    ).astype(model.dtype)
                    ecs = []
                    for j in range(cuts[li]):
                        if j in off_sets[li]:
                            x, nc = model._block_mix_step(
                                specs[j], per_layer[j], x, lane["edge"][j],
                                lane["lens"],
                            )
                            h2, combine = model._moe_pre_dispatch(per_layer[j], x)
                            x = x + moe_apply_experts(
                                h2, combine, per_layer[j]["moe"], cfg
                            )
                        else:
                            x, nc = model._block_step(
                                specs[j], per_layer[j], x, lane["edge"][j],
                                lane["lens"],
                            )
                        ecs.append(nc)
                    edges_new.append(ecs)
                    xs.append(x)
                # progressive tail: lane li joins the concatenated row
                # batch at layer cuts[li]; offsets slice its rows back out
                new_pools = {}
                states_new = [dict() for _ in range(n_lanes)]
                x_cat = pt_cat = len_cat = cap_cat = None
                offs = []
                joined = 0
                for layer in range(first, num_layers):
                    while joined < n_lanes and cuts[joined] == layer:
                        lane = lanes_c[joined]
                        if x_cat is None:
                            offs.append(0)
                            x_cat, pt_cat = xs[joined], pts[joined]
                            len_cat, cap_cat = lane["lens"], caps[joined]
                        else:
                            offs.append(x_cat.shape[0])
                            x_cat = jnp.concatenate([x_cat, xs[joined]], 0)
                            pt_cat = jnp.concatenate([pt_cat, pts[joined]], 0)
                            len_cat = jnp.concatenate([len_cat, lane["lens"]], 0)
                            cap_cat = jnp.concatenate([cap_cat, caps[joined]], 0)
                        joined += 1
                    if specs[layer][0] == "attn":
                        x_cat, nc = model._block_step(
                            specs[layer], per_layer[layer], x_cat,
                            pools_c[layer], len_cat,
                            paged=(pt_cat, cap_cat),
                        )
                        new_pools[layer] = {"kp": nc["kp"], "vp": nc["vp"]}
                    else:
                        st_cat = jax.tree.map(
                            lambda *a: jnp.concatenate(a, 0) if len(a) > 1 else a[0],
                            *(lanes_c[k]["state"][layer] for k in range(joined)),
                        )
                        x_cat, nc = model._block_step(
                            specs[layer], per_layer[layer], x_cat, st_cat,
                            len_cat,
                        )
                        for k in range(joined):
                            o, r = offs[k], lanes_c[k]["lens"].shape[0]
                            states_new[k][layer] = jax.tree.map(
                                lambda a, o=o, r=r: a[o:o + r], nc
                            )
                while joined < n_lanes:
                    # cut == num_layers: empty suffix — the edge output IS
                    # the final hidden; the lane joins after the last layer
                    if x_cat is None:
                        offs.append(0)
                        x_cat = xs[joined]
                    else:
                        offs.append(x_cat.shape[0])
                        x_cat = jnp.concatenate([x_cat, xs[joined]], 0)
                    joined += 1
                x_cat = rms_norm(x_cat, base["final_norm"], cfg.norm_eps)
                logits_cat = model._logits(base, x_cat)[:, 0]
                new_lanes = []
                for li in range(n_lanes):
                    o, r = offs[li], lanes_c[li]["lens"].shape[0]
                    new_lanes.append({
                        "logits": logits_cat[o:o + r].astype(jnp.float32),
                        "edge": edges_new[li],
                        "state": states_new[li],
                        "lens": lanes_c[li]["lens"] + 1,
                    })
                return (tuple(new_lanes), new_pools), tuple(toks_out)

            (lanes, pools), toks = jax.lax.scan(
                body, (lanes, pools), None, length=n_steps
            )
            toks = tuple(jnp.swapaxes(t, 0, 1) for t in toks)
            return toks, lanes, pools

        return donating_jit(fleet, donate_argnums=(2, 3))

    # ------------------------------------------------------------------
    # channel telemetry
    # ------------------------------------------------------------------

    def modeled_net_ms(self, prompt_len: int, n_decode: int) -> Dict[str, float]:
        """Channel cost of one split serving call (prefill ship + ping-pong).

        Zero when a side is empty in the LAYER dimension only if the stem /
        head still separate — the stem is always edge-resident here, so
        every call ships at least the embedded prompt.

        Expert-offload lanes add the per-MoE-block gather/scatter legs
        (the planner's pricing): one prefill round-trip over the prompt's
        top-k hidden states, plus one per decode token.
        """

        act_tok = self.cfg.d_model * 2.0  # bf16 activations
        out = interior_net_ms(self.channel, prompt_len * act_tok, act_tok, n_decode)
        if self.expert_offload:
            k = self.cfg.moe.num_experts_per_tok
            per_block = roundtrip_ms(
                self.channel, prompt_len * k * act_tok, prompt_len * act_tok
            ) + n_decode * roundtrip_ms(self.channel, k * act_tok, act_tok)
            out = dict(out)
            out["expert_ms"] = len(self.expert_offload) * per_block
            out["total_ms"] += out["expert_ms"]
        return out

    def record_chunk_bytes(self, prompt_len: int, n_decode: int) -> None:
        """Fold one robot-chunk's modeled channel bytes into the metrics.

        Per-leg ``channel.bytes_up`` / ``channel.bytes_down`` counters:
        the cut-activation leg ships every token's boundary activation up
        and the sampled token id back down; each offloaded MoE block adds
        an expert-gather leg (top-k hidden states up) and an expert-scatter
        leg (the mixture output down) over prompt + decode tokens.  No-op
        without an attached Observability handle.
        """

        if self.obs is None:
            return
        m = self.obs.metrics
        act_tok = self.cfg.d_model * 2.0
        tokens = prompt_len + n_decode
        m.counter("channel.bytes_up", leg="cut-activation").inc(
            int(tokens * act_tok)
        )
        m.counter("channel.bytes_down", leg="cut-activation").inc(
            int(n_decode * TOKEN_ID_BYTES)
        )
        if self.expert_offload:
            k = self.cfg.moe.num_experts_per_tok
            n_blocks = len(self.expert_offload)
            m.counter("channel.bytes_up", leg="expert-gather").inc(
                int(n_blocks * tokens * k * act_tok)
            )
            m.counter("channel.bytes_down", leg="expert-scatter").inc(
                int(n_blocks * tokens * act_tok)
            )


class PartitionedPolicy:
    """Drop-in ``CloudPolicy`` serving through a split model.

    Same observation-in / action-chunk-out contract; additionally records
    the modeled channel milliseconds of every call in ``net_ms_log``.
    """

    def __init__(
        self,
        executor: PartitionExecutor,
        tokenizer: EpisodeTokenizer,
        chunk_len: int = 8,
        n_joints: int = 7,
    ):
        self.executor = executor
        self.tok = tokenizer
        self.chunk_len = chunk_len
        self.n_joints = n_joints
        self.net_ms_log: List[float] = []
        n_steps = chunk_len * n_joints
        self._n_steps = n_steps
        self._prefill = jax.jit(
            lambda sp, b: executor.split_prefill(sp, b, extra=n_steps)
        )
        self._decode_chunk = jax.jit(
            lambda sp, logits, st: executor.split_decode_chunk(
                sp, logits, st, n_steps, tokenizer.action_base
            )[0]
        )

    def __call__(self, qd: np.ndarray, tau: np.ndarray) -> np.ndarray:
        obs = np.concatenate(
            [self.tok.encode_state(qd), self.tok.encode_state(tau)], axis=1
        )
        batch = {"tokens": jnp.asarray(obs)}
        sp = self.executor.split_params
        logits, state = self._prefill(sp, batch)
        toks = np.asarray(self._decode_chunk(sp, logits, state))
        self.net_ms_log.append(
            self.executor.modeled_net_ms(obs.shape[1], self._n_steps)["total_ms"]
        )
        self.executor.record_chunk_bytes(obs.shape[1], self._n_steps)
        return self.tok.decode_action(toks).reshape(-1, self.chunk_len, self.n_joints)
