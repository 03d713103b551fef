"""Hybrid-parallel GPT trainer: ONE jitted step covering dp, tp(mp), sp,
ZeRO(sharding) and pp.

This is the TPU-native equivalent of the reference's entire fleet hot loop
(SURVEY.md §3.1): fleet.distributed_model + PipelineParallel.train_batch +
DygraphShardingOptimizer.step + EagerReducer allreduces — all of which
become sharding declarations on a single compiled program.

Layout summary (mesh axes [dp, pp, sharding, sep, mp]):
  batch              P(("dp","sharding"))          global batch sharded
  mp weights         P(None,"mp") / P("mp",None)   Megatron TP
  activations        P(dp, None, "mp") at block boundaries when sp=True
  block stack        leading block axis P("pp")    scan+ppermute schedule
  optimizer slots    + "sharding" axis             ZeRO-1
"""

from __future__ import annotations

import functools
from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..nn import functional as F
from ..nn.functional_call import functional_call, state
from ..distributed.sharding_utils import (get_param_specs, shard_state,
                                          shard_opt_state_specs)
from ..distributed.pipelining import pipeline_apply
from ..distributed.meta_parallel.mp_layers import (parallel_cross_entropy,
                                                   _maybe_constraint)
from .gpt import GPTConfig, GPTForCausalLM

__all__ = ["GPTHybridTrainer", "GPTMoEHybridTrainer"]


from ..distributed.recompute import remat_wrap as _remat_wrap  # noqa: E402


class GPTHybridTrainer:
    # state-layout key map — subclasses (GPTMoEHybridTrainer) remap these
    # to their model's parameter names
    BLOCK_PREFIX = "gpt.h."
    KEY_WTE = "gpt.wte.weight"
    KEY_WPE = "gpt.wpe.weight"
    KEY_LNF_W = "gpt.ln_f.weight"
    KEY_LNF_B = "gpt.ln_f.bias"

    def __init__(self, cfg: GPTConfig, hcg, optimizer, microbatches: int = 1,
                 zero_stage: int = 1, vpp: int = 1):
        self.cfg = cfg
        self.hcg = hcg
        self.mesh = hcg.get_mesh()
        self.opt = optimizer
        self.M = microbatches
        self.S = hcg.get_pipe_parallel_world_size()
        # interleaved (VPP) schedule: V chunks per stage round-robin
        # (reference: PipelineParallelWithInterleave)
        self.V = max(vpp, 1)
        if self.S > 1 and cfg.num_layers % (self.S * self.V):
            raise ValueError(
                f"num_layers={cfg.num_layers} must divide evenly into "
                f"pp_degree={self.S} x vpp={self.V} chunks (reference "
                f"PipelineLayer uniform segmentation has the same "
                f"requirement)")
        if self.V > 1 and self.S > 1 and microbatches % self.S:
            raise ValueError("interleaved schedule needs microbatches "
                             "divisible by pp_degree")
        self.zero = zero_stage
        self.model = self._make_model(cfg)
        dt = getattr(cfg, "dtype", "float32")
        if dt != "float32":
            # cast BEFORE the layout snapshot so the stacked/sharded
            # state carries the configured dtype (masters stay f32 via
            # multi_precision); Layer.to validates the dtype string
            self.model.to(dtype=dt)
        self._build_state_layout()
        self._jit_step = None

    def _make_model(self, cfg):
        return GPTForCausalLM(cfg)

    def _get_template_block(self):
        return self.model.gpt.h[0]

    # ------------------------------------------------------------------
    def _build_state_layout(self):
        params, _ = state(self.model)
        specs = get_param_specs(self.model)
        L = self.cfg.num_layers
        # Stage-assign the embedding/head the SPMD way (reference:
        # meta_parallel/pp_layers.py — SharedLayerDesc ties wte between the
        # first and last stage and allreduces its grad between them).  In
        # the one-program schedule "ownership" is sharding: the vocab (and
        # position) tables extend their row sharding over the pp axis, so
        # each pipeline stage holds 1/S of the table instead of a full
        # replica, and the tied-weight grad merge (embed use + head use)
        # falls out of AD + GSPMD as exactly the reference's allreduce.
        wte_spec = tuple(specs[self.KEY_WTE])
        self._vocab_axes = wte_spec[0] if wte_spec else None
        import os as _os
        if self.S > 1 and _os.environ.get("PADDLE_TPU_PP_EXTEND_EMBED",
                                          "1") == "1":
            for k in (self.KEY_WTE, self.KEY_WPE):
                if k in specs:
                    old = tuple(specs[k])  # P(mp, None) from the embedding
                    d0 = old[0] if old else None
                    if d0 is None:
                        d0 = "pp"
                    elif isinstance(d0, tuple):
                        d0 = d0 + ("pp",)
                    else:
                        d0 = (d0, "pp")
                    specs[k] = P(d0, *old[1:])
            self._vocab_axes = specs[self.KEY_WTE][0]
        self.block_names = []   # suffix names within a block
        nonblock, blocks0 = {}, {}
        for k, v in params.items():
            if k.startswith(self.BLOCK_PREFIX):
                rest = k[len(self.BLOCK_PREFIX):]
                idx, suffix = rest.split(".", 1)
                if idx == "0":
                    blocks0[suffix] = None
            else:
                nonblock[k] = v
        self.block_names = sorted(blocks0)
        # stacked block params: [L, ...] for the plain schedule; for VPP,
        # [S*V, K, ...] with the chunk dim in stack_interleaved order
        # (device s's P('pp') slice = its round-robin chunks) and K = blocks
        # per chunk scanned by the stage body
        stacked = {}
        stacked_specs = {}
        interleave = self.S > 1 and self.V > 1
        K = L // (self.S * self.V) if interleave else None
        for suffix in self.block_names:
            per = [params[f"{self.BLOCK_PREFIX}{i}.{suffix}"]
                   for i in range(L)]
            inner = specs.get(f"{self.BLOCK_PREFIX}0.{suffix}", P())
            if interleave:
                order = [v * self.S + s for s in range(self.S)
                         for v in range(self.V)]
                stacked[suffix] = jnp.stack(
                    [jnp.stack(per[c * K:(c + 1) * K], axis=0)
                     for c in order], axis=0)
                stacked_specs[suffix] = P("pp", None, *tuple(inner))
            else:
                stacked[suffix] = jnp.stack(per, axis=0)
                stacked_specs[suffix] = P("pp" if self.S > 1 else None,
                                          *tuple(inner))
        self.params_nonblock = nonblock
        self.params_blocks = stacked
        self.specs_nonblock = {k: specs.get(k, P()) for k in nonblock}
        self.specs_blocks = stacked_specs
        self.template_block = self._get_template_block()

        # ZeRO slot specs (stage >= 1) — also grad specs for stage >= 2 and
        # param specs for stage 3 (reference: GroupShardedStage2/3 grad
        # reduce-scatter + param gather-on-use; here: sharding declarations
        # XLA lowers to exactly that collective pattern)
        shard_deg = self.hcg.get_sharding_parallel_world_size()
        if shard_deg > 1:
            self.slot_specs_nb = shard_opt_state_specs(
                self.specs_nonblock,
                {k: tuple(v.shape) for k, v in nonblock.items()},
                "sharding", shard_deg)
            self.slot_specs_blk = shard_opt_state_specs(
                self.specs_blocks,
                {k: tuple(v.shape) for k, v in stacked.items()},
                "sharding", shard_deg)
        else:
            self.slot_specs_nb = self.specs_nonblock
            self.slot_specs_blk = self.specs_blocks
        if self.zero >= 3 and shard_deg > 1:
            # stage 3: parameters THEMSELVES live sharded; GSPMD inserts
            # the all-gather at each use site
            self.specs_nonblock = self.slot_specs_nb
            self.specs_blocks = self.slot_specs_blk

    def batch_spec(self):
        axes = []
        if self.hcg.get_data_parallel_world_size() > 1:
            axes.append("dp")
        if self.hcg.get_sharding_parallel_world_size() > 1:
            axes.append("sharding")
        return P(tuple(axes) if axes else None)

    # ------------------------------------------------------------------
    def init_state(self):
        """Returns (params_nonblock, params_blocks, opt_nb, opt_blk) laid out
        on the mesh."""
        mesh = self.mesh
        pnb = shard_state(mesh, self.params_nonblock, self.specs_nonblock)
        pblk = shard_state(mesh, self.params_blocks, self.specs_blocks)
        opt_nb = self.opt.init(pnb)
        opt_blk = self.opt.init(pblk)
        shard_deg = self.hcg.get_sharding_parallel_world_size()
        if self.zero >= 1 and shard_deg > 1:
            slot_nb = self.slot_specs_nb
            slot_blk = self.slot_specs_blk
        else:
            slot_nb = self.specs_nonblock
            slot_blk = self.specs_blocks
        def lay_opt(ostate, pspecs):
            return {
                "step": ostate["step"],
                "slots": {k: shard_state(mesh, v, pspecs[k])
                          for k, v in ostate["slots"].items()},
                "master": {k: (None if v is None else
                               shard_state(mesh, v, pspecs[k]))
                           for k, v in ostate["master"].items()},
            }
        opt_nb = lay_opt(opt_nb, slot_nb)
        opt_blk = lay_opt(opt_blk, slot_blk)
        return pnb, pblk, opt_nb, opt_blk

    # ---- functional model pieces (non-block params used directly) ------
    def _take_table(self, pnb, key, idx):
        """Row lookup honoring the table's row sharding: row-sharded
        tables go through the GSPMD gather with an f32 scatter-
        accumulate bwd (_take_rows_f32grad) — a plain bf16 take's
        scatter-add bwd CHECK-crashes XLA in bf16 pp>1 hybrids, and the
        manual masked-lookup alternative (sharded_row_take) trips a psum
        replica-group CHECK on hybrid meshes (round-5 notes)."""
        spec = (self.specs_nonblock.get(key) or P())
        row_axes = tuple(spec)[0] if tuple(spec) else None
        if row_axes is None:
            return jnp.take(pnb[key], idx.astype(jnp.int32), axis=0)
        from ..distributed.meta_parallel.mp_layers import _take_rows_f32grad
        return _take_rows_f32grad(pnb[key], idx)

    def _embed(self, pnb, ids):
        cfg = self.cfg
        pos = jnp.arange(ids.shape[1])[None, :]
        x = self._take_table(pnb, self.KEY_WTE, ids) + \
            self._take_table(pnb, self.KEY_WPE, pos)
        # context parallel: activations ride the sep axis on the seq dim
        seq_axis = "sep" if getattr(cfg, "cp", False) else None
        return _maybe_constraint(x, P(None, seq_axis, None))

    def _final(self, pnb, x):
        cfg = self.cfg
        w = pnb.get(self.KEY_LNF_W)
        b = pnb.get(self.KEY_LNF_B)
        x = F.layer_norm(x, cfg.hidden_size, w, b, cfg.layer_norm_eps)
        # tied head: second use of the wte table (grads from both uses are
        # summed by AD — SharedLayerDesc semantics); logits stay sharded on
        # vocab over mp AND pp so no stage materializes the full [b,s,V]
        logits = jnp.einsum("bsh,vh->bsv", x, pnb[self.KEY_WTE])
        return _maybe_constraint(logits, P(None, None, self._vocab_axes))

    def _block_apply(self, blk_params, x):
        out, _ = functional_call(self.template_block, blk_params, {}, (x,),
                                 train=True)
        return out

    def _body(self, pblk_local, x):
        """Apply this stage's K blocks via scan (K = L/S local slice)."""
        def one(carry, bp):
            return self._block_apply(bp, carry), None
        out, _ = jax.lax.scan(one, x, pblk_local)
        return out

    # ---- pipeline carry hooks (overridden by GPTMoEHybridTrainer to
    # thread the gate aux loss through the schedule) --------------------
    def _pack_microbatches(self, mb):
        """[M, mb, s, h] hidden -> (activation pytree, x_spec pytree)."""
        seq_axis = "sep" if getattr(self.cfg, "cp", False) else None
        return mb, P(None, self.batch_spec()[0], seq_axis, None)

    def _pipeline_manual_axes(self):
        """Extra manual axes the pipeline shard_map must bind: the stage
        body runs ring/Ulysses collectives over sep when context
        parallelism is on (nested shard_map under pp is illegal)."""
        if getattr(self.cfg, "cp", False) and \
                self.hcg.get_sep_parallel_world_size() > 1:
            return frozenset({"sep"})
        return frozenset()

    def _unpack_pipeline_output(self, out):
        """activation pytree -> ([M, mb, s, h] hidden, extra loss term)."""
        return out, 0.0

    def _serial_forward(self, pblk, x):
        """S == 1 path: scan all blocks; -> (hidden, extra loss term)."""
        body = _remat_wrap(self._block_apply, self.cfg.remat)

        def one(carry, bp):
            return body(bp, carry), None
        x, _ = jax.lax.scan(one, x, pblk)
        return x, 0.0

    # ------------------------------------------------------------------
    def loss_fn(self, pnb, pblk, ids, labels):
        cfg = self.cfg
        x = self._embed(pnb, ids)
        if self.S > 1:
            b, s, h = x.shape
            M = self.M
            mb, x_spec = self._pack_microbatches(x.reshape(M, b // M, s, h))
            if self.V > 1:
                from ..distributed.pipelining import \
                    pipeline_apply_interleaved
                out = pipeline_apply_interleaved(
                    self._body, pblk, mb, self.mesh, self.S, self.V,
                    remat=cfg.remat, x_spec=x_spec,
                    param_inner_specs=self.specs_blocks,
                    extra_manual_axes=self._pipeline_manual_axes())
            else:
                out = pipeline_apply(self._body, pblk, mb, self.mesh, self.S,
                                     remat=cfg.remat, x_spec=x_spec,
                                     param_inner_specs=self.specs_blocks,
                                     extra_manual_axes=self._pipeline_manual_axes())
            hidden, extra = self._unpack_pipeline_output(out)
            x = hidden.reshape(b, s, h)
        else:
            x, extra = self._serial_forward(pblk, x)
        logits = self._final(pnb, x)
        per_tok = parallel_cross_entropy(logits, labels,
                                         mp_axis=self._vocab_axes)
        return jnp.mean(per_tok) + extra

    def build_step(self):
        opt = self.opt
        zero2 = (self.zero >= 2 and
                 self.hcg.get_sharding_parallel_world_size() > 1)

        def step(pnb, pblk, opt_nb, opt_blk, ids, labels, lr):
            loss, (g_nb, g_blk) = jax.value_and_grad(
                self.loss_fn, argnums=(0, 1))(pnb, pblk, ids, labels)
            if zero2:
                # stage 2: materialize grads SHARDED — XLA turns the dp/
                # sharding grad all-reduce into reduce-scatter + the update
                # math runs on 1/degree of each tensor
                g_nb = {k: _maybe_constraint(v, self.slot_specs_nb[k])
                        for k, v in g_nb.items()}
                g_blk = {k: _maybe_constraint(v, self.slot_specs_blk[k])
                         for k, v in g_blk.items()}
            new_nb, opt_nb = opt.update(g_nb, opt_nb, pnb, lr=lr)
            new_blk, opt_blk = opt.update(g_blk, opt_blk, pblk, lr=lr)
            if zero2 and self.zero < 3:
                # params stay unsharded in stages 1/2: bring the updated
                # values back to their declared layout
                new_nb = {k: _maybe_constraint(v, self.specs_nonblock[k])
                          for k, v in new_nb.items()}
                new_blk = {k: _maybe_constraint(v, self.specs_blocks[k])
                           for k, v in new_blk.items()}
            return new_nb, new_blk, opt_nb, opt_blk, loss

        return step

    def jit_step(self, donate: bool = True):
        if self._jit_step is None:
            step = self.build_step()
            self._jit_step = jax.jit(
                step, donate_argnums=(0, 1, 2, 3) if donate else ())
        return self._jit_step

    # ------------------------------------------------------------------
    def make_batch(self, batch: int, seq: Optional[int] = None, seed: int = 0):
        seq = seq or self.cfg.max_seq_len
        rng = np.random.RandomState(seed)
        ids = rng.randint(0, self.cfg.vocab_size, (batch, seq + 1))
        # keep the batch on host: put_global ingests numpy directly
        # (jnp.asarray first would bounce host->device->host on the
        # multi-controller path)
        x = np.ascontiguousarray(ids[:, :-1])
        y = np.ascontiguousarray(ids[:, 1:])
        seq_axis = "sep" if getattr(self.cfg, "cp", False) else None
        from ..distributed.sharding_utils import put_global
        bs = NamedSharding(self.mesh, P(self.batch_spec()[0], seq_axis))
        return put_global(x, bs), put_global(y, bs)

    def train_step(self, state_tuple, ids, labels):
        pnb, pblk, onb, oblk = state_tuple
        lr = jnp.asarray(self.opt.get_lr(), jnp.float32)
        # traced with the mesh in scope: the model's sharding
        # constraints bind to it, and kernel routes can see that this
        # program is partitioned by XLA (kernels/routing.py)
        with jax.set_mesh(self.mesh):
            pnb, pblk, onb, oblk, loss = self.jit_step()(
                pnb, pblk, onb, oblk, ids, labels, lr)
        return (pnb, pblk, onb, oblk), loss


class GPTMoEHybridTrainer(GPTHybridTrainer):
    """Hybrid-parallel GPT-MoE trainer: dp x pp x ZeRO x EP in ONE jitted
    step (reference: paddle.incubate.distributed.models.moe GPT over the
    fleet expert group, composed with PipelineParallel /
    DygraphShardingOptimizer — SURVEY.md §2.3 EP + Hybrid rows).

    Experts shard over the first-class ``ep`` mesh axis (MoELayer defaults
    its group to HCG.get_expert_parallel_group() when ep_degree > 1), so
    expert dispatch einsums compile to all-to-all over ep while blocks
    pipeline over pp and the batch shards over dp/sharding.

    Blocks must be uniform (``cfg.moe_every == 1``) — the fused pipeline
    schedule's requirement, same as the reference PipelineLayer uniform
    segmentation.

    The gate load-balance aux losses ride the pipeline INSIDE the
    activation pytree ({"h": hidden, "aux": scalar}): each stage adds its
    blocks' aux terms as the microbatch flows through, and the last stage
    emits the per-microbatch totals — the one-program SPMD form of the
    reference's cross-stage aux-loss reduction.  With microbatches > 1 the
    batch aux is the mean of per-microbatch aux values (a documented,
    standard estimator deviation: the balance loss is nonlinear in the
    token set; with M=1 it equals the serial value exactly).
    """

    BLOCK_PREFIX = "h."
    KEY_WTE = "wte.weight"
    KEY_WPE = "wpe.weight"
    KEY_LNF_W = "ln_f.weight"
    KEY_LNF_B = "ln_f.bias"

    def __init__(self, cfg, hcg, optimizer, microbatches: int = 1,
                 zero_stage: int = 1, vpp: int = 1):
        if cfg.moe_every != 1:
            raise ValueError(
                "GPTMoEHybridTrainer needs uniform blocks: set "
                "cfg.moe_every = 1 (every block MoE) — the fused pipeline "
                "schedule requires structurally identical stages, like the "
                "reference PipelineLayer's uniform segmentation")
        # ep x mp composition: with a model-parallel degree in the fleet
        # config, experts default to internal tensor parallelism over the
        # mp axis (reference: the fleet call site passes
        # hcg.get_model_parallel_group() into MoELayer(mp_group))
        if cfg.mp_group is None and hcg.get_model_parallel_world_size() > 1:
            cfg.mp_group = "mp"
        super().__init__(cfg, hcg, optimizer, microbatches=microbatches,
                         zero_stage=zero_stage, vpp=vpp)

    def _make_model(self, cfg):
        from .gpt_moe import GPTMoEForCausalLM
        return GPTMoEForCausalLM(cfg)

    def _get_template_block(self):
        return self.model.h[0]

    # ---- MoE stage body: hidden + aux accumulator --------------------
    def _block_apply(self, blk_params, x):
        out, nb = functional_call(self.template_block, blk_params, None,
                                  (x,), train=True)
        aux = jnp.zeros((), jnp.float32)
        for k, v in nb.items():
            if k.endswith("aux_loss"):
                aux = aux + v
        return out, aux

    def _body(self, pblk_local, carry):
        def one(c, bp):
            out, aux_inc = self._block_apply(bp, c["h"])
            return {"h": out, "aux": c["aux"] + aux_inc}, None
        out, _ = jax.lax.scan(one, carry, pblk_local)
        return out

    def _pack_microbatches(self, mb):
        M = mb.shape[0]
        return ({"h": mb, "aux": jnp.zeros((M,), jnp.float32)},
                {"h": P(None, self.batch_spec()[0]), "aux": None})

    def _unpack_pipeline_output(self, out):
        return out["h"], self.cfg.aux_weight * jnp.mean(out["aux"])

    def _serial_forward(self, pblk, x):
        # per-block remat inside the scan — same granularity as the base
        # class (one recompute chunk per block, not one for all L blocks)
        blk = _remat_wrap(self._block_apply, self.cfg.remat)

        def one(c, bp):
            out, aux_inc = blk(bp, c["h"])
            return {"h": out, "aux": c["aux"] + aux_inc}, None

        carry, _ = jax.lax.scan(
            one, {"h": x, "aux": jnp.zeros((), jnp.float32)}, pblk)
        return carry["h"], self.cfg.aux_weight * carry["aux"]
