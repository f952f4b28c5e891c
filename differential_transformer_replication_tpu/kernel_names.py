"""The names of the Pallas kernels, one per ``pallas_call`` site.

``pl.pallas_call(..., name=X)`` makes ``X`` the HLO instruction's own
name (``%flash_fwd_tm_packed.3 = ... custom-call(...)``, under ``vmap``
``%vmap_fused_add_norm_fwd_.1``), which is the name of the kernel's
events in a profiler trace. Without it the instruction is named after
whatever encloses the call (``%jvp__.N``, ``%transpose_jvp___.N``) and
no reader can tell one kernel from another. ``obs/xprof.py`` builds its
buckets from the families below, and the benchmark's readers
(``benchmark/lib/xplane.py``, whose needles are frozen) match them as
substrings, so the names keep to these rules:

- every flash kernel holds ``flash``, forward kernels start
  ``flash_fwd`` and backward kernels ``flash_bwd``;
- every FFN kernel holds ``fused_ffn``;
- every norm/residual kernel holds ``fused_add_norm`` and not
  ``fused_ffn``;
- the decode kernel holds ``_dattn_``;
- the cache-write kernel holds none of these (the benchmark's reader
  books it under ``pallas``, ``obs/xprof.py`` under ``kv_write``);
- the state-space kernels start ``ssm_`` (``pallas`` there, ``ssm`` here);
- the delta-rule kernel starts ``kda_`` (``pallas`` there, ``kda`` here);
- the experts' grouped product starts ``moe_`` (``pallas`` there, ``moe``
  here);
- the latent ring's decode read starts ``mla_`` (``pallas`` there, ``mla``
  here);
- no name holds a needle of another family.

Standard library only, at the top of the package: ``ops/`` (which
imports jax) and ``obs/xprof.py`` (which does not) both import it.
"""

# ops/flash.py
FLASH_FWD = "flash_fwd"                      # one K/V block a row of Q blocks
FLASH_FWD_TILED = "flash_fwd_tiled"          # K/V-tiled grid (long T)
FLASH_FWD_CHUNK = "flash_fwd_chunk"          # a Q chunk at an offset (ring)
FLASH_FWD_TM = "flash_fwd_tm"                # token-major
FLASH_FWD_TM_PACKED = "flash_fwd_tm_packed"  # token-major, packed projections
FLASH_BWD_DQ = "flash_bwd_dq"
FLASH_BWD_DKV = "flash_bwd_dkv"
FLASH_BWD_DQ_TILED = "flash_bwd_dq_tiled"
FLASH_BWD_DKV_TILED = "flash_bwd_dkv_tiled"
FLASH_BWD_FUSED = "flash_bwd_fused"          # dq, dk, dv in one kernel
FLASH_BWD_TM = "flash_bwd_tm"
FLASH_BWD_TM_PACKED = "flash_bwd_tm_packed"

# ops/fused_ffn.py
FUSED_FFN_FWD = "fused_ffn_fwd"
FUSED_FFN_BWD = "fused_ffn_bwd"

# ops/fused_norm_residual.py (with and without the residual input)
FUSED_ADD_NORM_FWD = "fused_add_norm_fwd"
FUSED_ADD_NORM_BWD = "fused_add_norm_bwd"

# ops/decode_attention.py (all four entry points share one call)
DECODE_ATTENTION = "decode_dattn_fwd"

# ops/ring_attention.py (the afmoe family's decode step: a row's live ring
# blocks of every K/V head a grid step, grouped query heads)
RING_GQA_DECODE = "ring_gqa_decode_fwd"

# ops/mla.py (the deepseek_v2 family's decode step: a row's live blocks of
# the ring of latents, each read once for all heads, absorbed queries)
MLA_LATENT_DECODE = "mla_latent_decode_fwd"
# (a prefill chunk's: a head's queries of the whole chunk over the ring's
# blocks, each widened to that head's keys and values on the chip)
MLA_CHUNK_WIDENED = "mla_chunk_widened_fwd"

# ops/kv_write.py (one call a cache leaf: K, V and the int8 scale planes)
KV_ROW_WRITE = "kv_row_write"

# ops/ssm.py (the jamba family's Mamba mixers: the chunk's scan, and the
# decode step's one-token update of the active slots, in place in the pool)
SSM_SCAN_FWD = "ssm_scan_fwd"
SSM_STATE_UPDATE = "ssm_state_update"

# ops/ssd.py (the nemotron_h family's Mamba-2 mixers: the decode step's
# one-token update of the active slots' states, a (slot, group of heads) a
# grid step, in place in the pool)
SSD_STATE_UPDATE = "ssm_ssd_state_update"

# ops/kda.py (the kimi_linear family's KDA mixers: the decode step's
# one-token update of the active slots' states, in place in the pool)
KDA_STATE_UPDATE = "kda_state_update"

# ops/moe.py (the kimi_linear family's routed experts: one call a grouped
# product, a row tile of one expert a grid step)
MOE_GROUPED_MATMUL = "moe_grouped_matmul"

RING = (RING_GQA_DECODE,)
MLA = (MLA_LATENT_DECODE, MLA_CHUNK_WIDENED)
FLASH = (
    FLASH_FWD, FLASH_FWD_TILED, FLASH_FWD_CHUNK, FLASH_FWD_TM,
    FLASH_FWD_TM_PACKED, FLASH_BWD_DQ, FLASH_BWD_DKV, FLASH_BWD_DQ_TILED,
    FLASH_BWD_DKV_TILED, FLASH_BWD_FUSED, FLASH_BWD_TM, FLASH_BWD_TM_PACKED,
)
FUSED_FFN = (FUSED_FFN_FWD, FUSED_FFN_BWD)
FUSED_NORM = (FUSED_ADD_NORM_FWD, FUSED_ADD_NORM_BWD)
DECODE = (DECODE_ATTENTION,)
KV_WRITE = (KV_ROW_WRITE,)
SSM = (SSM_SCAN_FWD, SSM_STATE_UPDATE, SSD_STATE_UPDATE)
KDA = (KDA_STATE_UPDATE,)
MOE = (MOE_GROUPED_MATMUL,)

#: family -> its kernels' names; every ``pallas_call`` under ``ops/``
#: passes one of these as ``name=``
FAMILIES = {
    "flash_attention": FLASH,
    "fused_ffn": FUSED_FFN,
    "fused_norm": FUSED_NORM,
    "decode_attention": DECODE,
    "kv_write": KV_WRITE,
    "ssm": SSM,
    "kda": KDA,
    "moe": MOE,
    "ring_attention": RING,
    "mla": MLA,
}
ALL = (FLASH + FUSED_FFN + FUSED_NORM + DECODE + KV_WRITE + SSM + KDA + MOE
       + RING + MLA)
