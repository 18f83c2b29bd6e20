"""The names the program gives its own work: scopes in a device trace,
and the values a rematerialised block always keeps.

Every ``jax.named_scope`` of the package takes its name from here, and
the device-trace reduction (``obs/profile.py:scope_of``) files an
operation under the innermost of :data:`SCOPES` in its ``op_name``: a
scope that is not in this module would silently be filed under a flax
module's name.  Metadata only: a scope is never a flax submodule, so
parameter trees and checkpoints do not know these names.

No imports: the models, the collectives and the launcher-side obs plane
all read this module.
"""

GRAD_ALLREDUCE = "grad_allreduce"      # DistributedOptimizer: the reduction
                                       # with its packing and unpacking
ALLREDUCE = "allreduce"                # every traced allreduce, whatever XLA
                                       # calls it (all-reduce.81, psum.220)
OPTIMIZER_UPDATE = "optimizer_update"  # the wrapped optimizer's update
ATTN = "attn"                          # a block's attention half
SSM = "ssm"                            # a block's state-space mixer half,
                                       # Mamba-2's or Mamba-1's: in_proj,
                                       # conv, scan, gate, out_proj
SSD_SCAN = "ssd_scan"                  # Mamba-2's scan alone, inside ssm
SSM_PREP = "ssm_prep"                  # inside ssm, Mamba-2's: the filter,
                                       # its bias and silu over xBC and the
                                       # split into x, B and C, a float32
                                       # elementwise chain between in_proj
                                       # and the scan (dt's softplus stays
                                       # outside it)
SSM_NORM = "ssm_norm"                  # inside ssm, Mamba-2's: the gate
                                       # y * silu(z) and the RMS norm by
                                       # group, a float32 elementwise chain
                                       # between the scan and out_proj.
                                       # Both chains are the kernel pairs of
                                       # ops/ssm_chain.py where the shape
                                       # allows (gauge
                                       # ssm_chain.kernel_layers), else
                                       # XLA's fusions
SELECTIVE_SCAN = "selective_scan"      # Mamba-1's scan alone, inside ssm
GMU = "gmu"                            # a block's gated-memory-unit half:
                                       # in_proj, the product with the scan
                                       # memory another layer made, out_proj
SHORT_CONV = "short_conv"              # a block's gated-short-convolution
                                       # half: in_proj, the gates and the
                                       # filter, out_proj
SHORT_CONV_FILTER = "short_conv_filter"  # inside short_conv: the
                                       # elementwise chain alone (B * u,
                                       # the taps, C *)
KDA = "kda"                            # a block's Kimi-Delta-Attention half:
                                       # the projections, filters, norms,
                                       # gates, the chunk rule, the gated
                                       # norm, o_proj
KDA_PREP = "kda_prep"                  # inside kda: the float32 elementwise
                                       # chain between the projections and
                                       # the rule (three filters with silu,
                                       # two L2 norms, softplus, the decay):
                                       # the kernels kda_prep_fwd and
                                       # kda_prep_bwd of ops/kda_prep.py
                                       # where the shape allows (gauge
                                       # kda.prep_kernel_layers), else XLA's
                                       # fusions; beta's sigmoid either way
KDA_SCAN = "kda_scan"                  # inside kda: the chunk rule alone
GDN = "gdn"                            # a block's Gated-DeltaNet half: the
                                       # two projections, the filter, the
                                       # L2 norms, the decay, the chunk
                                       # rule, the gated norm, out_proj
GDN_PREP = "gdn_prep"                  # inside gdn: the float32 elementwise
                                       # chain between the projections and
                                       # the rule (one filter over q, k and
                                       # v with silu, two L2 norms, the
                                       # decay a head, beta's sigmoid):
                                       # XLA's fusions
GDN_SCAN = "gdn_scan"                  # inside gdn: the rule alone, with
                                       # what spreads a head's decay over
                                       # its channels and a key head over
                                       # its value heads for ops/kda.py's
                                       # kernels
GDN_SPREAD = "gdn_spread"              # inside gdn_scan: that spreading
                                       # alone (and the sums that take its
                                       # gradients back), so gdn_scan less
                                       # gdn_spread is the rule's kernels
MLA_PROJ = "mla_proj"                  # latent attention, inside attn: the
                                       # two low-rank paths, their norms,
                                       # RoPE, the shared rotary key
ATTN_PREP = "attn_prep"                # inside attn: what stands between
                                       # the fused q/k/v matmul and the
                                       # attention call: the split into
                                       # heads, the norm over each head of
                                       # q and k, the rotation.  The
                                       # kernels attn_prep_fwd and
                                       # attn_prep_bwd of ops/attn_prep.py
                                       # (which write head-major, as the
                                       # flash kernels read) where its plan
                                       # takes the call (gauge
                                       # attn_prep.kernel_layers), else
                                       # XLA's fusions
ATTN_WINDOW = "attn_window"            # inside attn: the attention call of
                                       # a layer that has a window, so its
                                       # flash kernels carry the name
ATTN_GATE = "attn_gate"                # inside attn: the output gate's
                                       # matmul (where the gate has a
                                       # matrix of its own and is not part
                                       # of the query projection), sigmoid
                                       # and product
ATTN_CROSS = "attn_cross"              # inside attn: the attention call of
                                       # a layer that reads the keys and
                                       # values another layer made
ATTN_DIFF = "attn_diff"                # inside attn: differential
                                       # attention's lambda, subtraction,
                                       # sub-norm and scale (the attention
                                       # calls stay outside it)
ATTN_BLOCK_DIFFUSION = "attn_block_diffusion"  # inside attn: the
                                       # attention call under the
                                       # block-diffusion mask (a noised
                                       # copy beside the clean one)
DIFFUSION_NOISE = "diffusion_noise"    # the step's noising: the levels,
                                       # the masked positions, the noised
                                       # copy laid beside the clean one
                                       # (models/block_diffusion.py)
HC_COEFF = "hc_coeff"                  # hyper-connections, ahead of each
                                       # half and outside its scope: the
                                       # norm over all the streams'
                                       # channels, the float32 matmul, the
                                       # sigmoids, the clamped exponential
                                       # and Sinkhorn's iteration
HC_READ = "hc_read"                    # the read-out: the streams' weighted
                                       # sum a half's norm and branch read
HC_WRITE = "hc_write"                  # the write-back: the streams mixed
                                       # by the doubly stochastic map plus
                                       # the branch's output a stream, the
                                       # float32 sum and the store
MLP = "mlp"                            # a block's MLP half
MOE_ROUTE = "moe_route"                # router matmul, scores, top-k, the
                                       # sort by expert: inside mlp, or at
                                       # the block's top where the router
                                       # reads the layer's input
MOE_LOGITS = "moe_logits"              # inside moe_route: the float32
                                       # router matmul and the score rule's
                                       # sigmoid
MOE_TOPK = "moe_topk"                  # inside moe_route: top-k, the chosen
                                       # scores (the sigmoid rule's a select
                                       # over the experts),
                                       # the weights' normalisation (or
                                       # softmax over the chosen), the
                                       # scaling
MOE_SORT = "moe_sort"                  # inside moe_route: the keys, the
                                       # stable argsort by expert, the two
                                       # counts (group sizes, load) by
                                       # comparison
MOE_UNSORT = "moe_unsort"              # inside moe_route: the second sort
                                       # that inverts the first
MOE_BALANCE = "moe_balance"            # inside moe_route: the load-balance
                                       # loss (full softmax, its mean)
MOE_DISPATCH = "moe_dispatch"          # inside mlp: rows gathered into
                                       # expert order and back
MOE_ROWS_IN = "moe_rows_in"            # inside moe_dispatch: the tokens'
                                       # rows (backward: their gradients'
                                       # rows) gathered into expert order
MOE_ROWS_OUT = "moe_rows_out"          # inside moe_dispatch: the experts'
                                       # rows gathered back by choice and
                                       # their weighted k-way float32 sum
MOE_EXPERTS = "moe_experts"            # inside mlp: the grouped matmuls
MOE_CAST = "moe_cast"                  # inside moe_experts: the float32
                                       # masters' cast to the compute dtype
                                       # (backward: the gradients' cast back)
MOE_GATE = "moe_gate"                  # inside moe_experts: act(gate) * up
                                       # between the two grouped matmuls
MOE_SHARED = "moe_shared"              # inside mlp: the shared expert and,
                                       # where it has one, its sigmoid gate
MTP = "mtp"                            # the multi-token-prediction module
                                       # and its pass through the head
EMBED = "embed"                        # token and position embedding
HEAD = "head"                          # final norm and output projection
STEM = "stem"                          # ResNet's first conv and pool
KV_GATHER = "kv_gather"                # paged decode: pages -> contiguous KV
KV_SCATTER = "kv_scatter"              # paged prefill: KV -> pages
SAMPLE = "sample"                      # the token pick

# What a Pallas kernel's forward made, named (``checkpoint_name``) inside
# its ``custom_vjp`` forward rule so that the value returned and the
# residual are one named value.  A rematerialised block keeps these
# whatever its policy (``models/transformer.py:block_remat_policy``):
# the recompute finds them and does not run the kernel a second time.
FLASH_OUT = "flash_out"                # flash attention's o
FLASH_LSE = "flash_lse"                # and its log-sum-exp rows
SSD_OUT = "ssd_out"                    # the state-space scan's y
SSD_STATES = "ssd_states"              # and its chunk-start states
SSCAN_OUT = "sscan_out"                # the selective scan's y
SSCAN_STATES = "sscan_states"          # and its time-block-start states
KDA_OUT = "kda_out"                    # the gated delta rule's o
KDA_STATES = "kda_states"              # and its group-start states
KERNEL_OUTPUTS = (FLASH_OUT, FLASH_LSE, SSD_OUT, SSD_STATES, SSCAN_OUT,
                  SSCAN_STATES, KDA_OUT, KDA_STATES)

SCOPES = (GRAD_ALLREDUCE, ALLREDUCE, OPTIMIZER_UPDATE, ATTN, MLA_PROJ,
          ATTN_WINDOW, ATTN_GATE, ATTN_CROSS, ATTN_DIFF, SSM, SSD_SCAN,
          SELECTIVE_SCAN, GMU, SHORT_CONV, SHORT_CONV_FILTER, KDA,
          KDA_PREP, KDA_SCAN, MLP,
          MOE_ROUTE, MOE_BALANCE, MOE_DISPATCH, MOE_EXPERTS, MOE_SHARED,
          MTP, EMBED, HEAD, STEM, KV_GATHER, KV_SCATTER, SAMPLE,
          MOE_LOGITS, MOE_TOPK, MOE_SORT, MOE_UNSORT, MOE_ROWS_IN,
          MOE_ROWS_OUT, MOE_CAST, MOE_GATE, ATTN_BLOCK_DIFFUSION,
          DIFFUSION_NOISE, HC_COEFF, HC_READ, HC_WRITE, SSM_NORM,
          SSM_PREP, ATTN_PREP, GDN, GDN_PREP, GDN_SCAN, GDN_SPREAD)
