"""Configuration core: defaults, control-string codec, derived hyperparameters.

Parity target: the reference's global ``cfg`` dict (``src/config.py:3-6``,
``src/config.yml``) and ``process_control()`` (``src/utils.py:113-215``).
Unlike the reference this module is purely functional -- no import-time global
mutable state; entry points build a cfg dict and pass it explicitly.

The 9-field control string
``fed_numusers_frac_datasplit_modelsplit_modelmode_norm_scale_mask``
(e.g. ``1_100_0.1_iid_fix_a2-b8_bn_1_1``) doubles as the experiment tag
(``src/train_classifier_fed.py:30,41-42``).
"""

from __future__ import annotations

import copy
import math
from typing import Any, Dict, List

import numpy as np

# Width multiplier per complexity level (ref src/utils.py:114).
MODEL_SPLIT_RATE: Dict[str, float] = {"a": 1.0, "b": 0.5, "c": 0.25, "d": 0.125, "e": 0.0625}

CONTROL_KEYS = (
    "fed",
    "num_users",
    "frac",
    "data_split_mode",
    "model_split_mode",
    "model_mode",
    "norm",
    "scale",
    "mask",
)

# Canonical name registries, kept here (jax-free) so offline analysis tooling
# can validate tags without importing the model/data stacks.  models/ and
# data/ import these rather than re-declaring them.
NORM_TYPES = ("bn", "in", "ln", "gn", "none")
#: THE table of decoder-only language-model families: name -> the published
#: shape its ``models/<name>.py`` (``make_<name>``) builds at the GLOBAL widths.
#: ``MODEL_NAMES``, ``LM_MODEL_NAMES``, ``process_control``'s ``cfg[<name>]`` and
#: what ``models.make_model`` can build are all read off it: a new family is its
#: module and one row here.
DECODER_FAMILIES: Dict[str, Dict[str, Any]] = {
    # Kanana-2-30B-A3B (model_type deepseek_v3): the published shape
    # (huggingface.co/kakaocorp/kanana-2-30b-a3b-instruct-2601 config.json).
    # ``expert_share`` = [index, of]: this process holds experts
    # [index * n/of, (index + 1) * n/of) of every expert layer -- one chip's
    # share of an ``of``-way expert-parallel deployment; the router keeps all
    # ``n_routed_experts`` columns.  [0, 1] holds every expert.
    "kanana2": {
        "hidden_size": 2048,
        "num_hidden_layers": 48,
        "first_k_dense_replace": 1,
        "intermediate_size": 6144,
        "moe_intermediate_size": 768,
        "n_routed_experts": 128,
        "n_shared_experts": 2,
        "num_experts_per_tok": 6,
        "routed_scaling_factor": 2.448,
        "num_attention_heads": 32,
        "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64,
        "v_head_dim": 128,
        "kv_lora_rank": 512,
        "rope_theta": 1000000.0,
        "rms_norm_eps": 1e-6,
        "expert_share": [0, 1],
    },
    # LFM2-8B-A1B (model_type lfm2_moe): the published shape
    # (huggingface.co/LiquidAI/LFM2-8B-A1B config.json); ``head_dim`` =
    # hidden_size / num_attention_heads and ``conv_dim`` = hidden_size are the
    # family's convention, not keys of that file.  ``expert_share`` as above.
    "lfm2": {
        "hidden_size": 2048,
        "num_hidden_layers": 24,
        "layer_types": ["conv", "conv", "full_attention", "conv", "conv", "conv",
                        "full_attention", "conv", "conv", "conv", "full_attention",
                        "conv", "conv", "conv", "full_attention", "conv", "conv",
                        "conv", "full_attention", "conv", "conv", "full_attention",
                        "conv", "conv"],
        "num_dense_layers": 2,
        "intermediate_size": 7168,
        "moe_intermediate_size": 1792,
        "num_experts": 32,
        "num_experts_per_tok": 4,
        "routed_scaling_factor": 1.0,
        "num_attention_heads": 32,
        "num_key_value_heads": 8,
        "head_dim": 64,
        "conv_dim": 2048,
        "conv_L_cache": 3,
        "rope_theta": 1000000.0,
        "norm_eps": 1e-5,
        "expert_share": [0, 1],
    },
    # Keye-VL-2.0-30B-A3B's language model (model_type KeyeVL2): the
    # published shape (huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B
    # config.json); ``index_*`` are its ``sa_config`` (indexer_head_dim,
    # indexer_num_heads on one key head, topk) under DeepSeek-V3.2's flat
    # names, because an override merges one level deep.  ``expert_share`` as
    # above; the benchmark's cut is 5 layers and [0, 16] (8 of 128 experts).
    "keye": {
        "hidden_size": 2048,
        "num_hidden_layers": 48,
        "moe_intermediate_size": 768,
        "num_experts": 128,
        "num_experts_per_tok": 8,
        "num_attention_heads": 32,
        "num_key_value_heads": 4,
        "head_dim": 128,
        "index_n_heads": 16,
        "index_head_dim": 64,
        "index_topk": 2048,
        "rope_theta": 10000000.0,
        "rms_norm_eps": 1e-6,
        "expert_share": [0, 1],
    },
    # Ouro-2.6B (model_type ouro, the LoopLM of arXiv:2510.25741): the
    # published shape (huggingface.co/ByteDance/Ouro-2.6B config.json).  The
    # whole layer stack runs ``total_ut_steps`` times on the same weights;
    # ``early_exit_threshold`` acts at inference only.  ``exit_entropy_beta``
    # (the weight of the exit distribution's entropy in the training loss,
    # the paper's stage-I objective under a uniform prior) is not a key of
    # that file.  The benchmark's cut is 4 layers.
    "ouro": {
        "hidden_size": 2048,
        "num_hidden_layers": 48,
        "intermediate_size": 5632,
        "num_attention_heads": 16,
        "num_key_value_heads": 16,
        "head_dim": 128,
        "total_ut_steps": 4,
        "early_exit_threshold": 1.0,
        "exit_entropy_beta": 0.1,
        "rope_theta": 1000000.0,
        "rms_norm_eps": 1e-6,
    },
    # Laguna-XS.2 (model_type laguna): the published shape
    # (huggingface.co/poolside/Laguna-XS.2 config.json).  The three lists are
    # the published ones (a full-attention layer of 48 query heads at 0, 4, 8,
    # ..., sliding layers of 64 between; layer 0 dense), written as their rule;
    # the model reads the lists and assumes no period.  ``rope_parameters`` is
    # one nested group (an override replaces it whole).  ``expert_share`` as
    # above; the benchmark's cut is layers 0-4 and [0, 16] (16 of 256 experts).
    "laguna": {
        "hidden_size": 2048,
        "num_hidden_layers": 40,
        "layer_types": ["sliding_attention" if i % 4 else "full_attention" for i in range(40)],
        "mlp_layer_types": ["sparse" if i else "dense" for i in range(40)],
        "num_attention_heads_per_layer": [64 if i % 4 else 48 for i in range(40)],
        "num_attention_heads": 48,
        "num_key_value_heads": 8,
        "head_dim": 128,
        "sliding_window": 512,
        "rope_parameters": {
            "full_attention": {
                "rope_theta": 500000.0, "rope_type": "yarn", "factor": 64.0,
                "original_max_position_embeddings": 4096, "beta_slow": 1.0, "beta_fast": 64.0,
                "attention_factor": 1.4158883083359672, "partial_rotary_factor": 0.5},
            "sliding_attention": {
                "rope_theta": 10000.0, "rope_type": "default", "partial_rotary_factor": 1.0},
        },
        "intermediate_size": 8192,
        "moe_intermediate_size": 512,
        "shared_expert_intermediate_size": 512,
        "num_experts": 256,
        "num_experts_per_tok": 8,
        "moe_routed_scaling_factor": 2.5,
        "rms_norm_eps": 1e-6,
        "expert_share": [0, 1],
    },
    # NVIDIA-Nemotron-3-Nano-30B-A3B (model_type nemotron_h, the Nemotron-H
    # hybrid of arXiv:2504.03624 with sparse feed-forwards): the published
    # shape (huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16
    # config.json).  By ``hybrid_override_pattern`` each layer is ONE
    # sub-block: ``M`` a Mamba-2 mixer, ``*`` grouped-query attention, ``E``
    # routed experts beside a shared one.  ``expert_share`` as above; the
    # benchmark's cut is layers 6-12 (``EMEMEM*``) and [0, 16] (8 of 128
    # experts).
    "nemotron_h": {
        "hidden_size": 2688,
        "num_hidden_layers": 52,
        "hybrid_override_pattern": "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME",
        "mamba_num_heads": 64,
        "mamba_head_dim": 64,
        "ssm_state_size": 128,
        "n_groups": 8,
        "conv_kernel": 4,
        "chunk_size": 128,
        "num_attention_heads": 32,
        "num_key_value_heads": 2,
        "head_dim": 128,
        "moe_intermediate_size": 1856,
        "moe_shared_expert_intermediate_size": 3712,
        "n_routed_experts": 128,
        "num_experts_per_tok": 6,
        "routed_scaling_factor": 2.5,
        "layer_norm_epsilon": 1e-5,
        "expert_share": [0, 1],
    },
    # Phi-4-mini-flash-reasoning (model_type phi4flash; the SambaY
    # decoder-hybrid-decoder of arXiv:2507.06607 with differential attention,
    # arXiv:2410.05258): the published shape
    # (huggingface.co/microsoft/Phi-4-mini-flash-reasoning config.json).
    # ``layer_types`` is the published rule written out (``mb_per_layer`` 2,
    # half = 16: even layers up to 16 ``mamba``, odd ones below 16
    # ``sliding``, 17 ``full``, then ``gmu`` / ``cross``;
    # ``models.phi4flash.layer_types``); layer ``i`` of ``layer_types`` is
    # PUBLISHED layer ``layer_offset + i`` (differential attention's constant
    # reads it).  ``d_state``, ``d_conv``, ``expand``, ``dt_rank`` are the
    # phi4flash configuration class's defaults (the published file has no key
    # for them).  The benchmark's cut is layers 16-19 (``mamba``,
    # ``full``, ``gmu``, ``cross``) at ``layer_offset`` 16.
    "phi4flash": {
        "hidden_size": 2560,
        "num_hidden_layers": 32,
        "mb_per_layer": 2,
        "layer_types": ["mamba", "sliding"] * 8 + ["mamba", "full"] + ["gmu", "cross"] * 7,
        "layer_offset": 0,
        "num_attention_heads": 40,
        "num_key_value_heads": 20,
        "sliding_window": 512,
        "intermediate_size": 10240,
        "d_state": 16,
        "d_conv": 4,
        "expand": 2,
        "dt_rank": 160,
        "layer_norm_eps": 1e-5,
    },
}
MODEL_NAMES = ("conv", "resnet18", "resnet34", "resnet50", "resnet101",
               "resnet152", "transformer") + tuple(DECODER_FAMILIES)
#: the families that train on token rows (next- or masked-token loss): the
#: drivers' and engines' LM paths key on this, not on one family's name
LM_MODEL_NAMES = ("transformer",) + tuple(DECODER_FAMILIES)
# Feature-axis value registries (ISSUE 18): THE declared domains of the
# engine/placement/store/pod axes, consumed by the axis validators below and
# by staticcheck's config-lattice pass (staticcheck/lattice.py enumerates
# every combination and proves it is either audited-green or refused here).
STRATEGIES = ("masked", "grouped", "sliced")
DATA_PLACEMENTS = ("replicated", "sharded")
LEVEL_PLACEMENTS = ("span", "slices")
CLIENT_STORES = ("eager", "stream")
VISION_DATASETS = ("MNIST", "FashionMNIST", "EMNIST", "CIFAR10", "CIFAR100")
FOLDER_DATASETS = ("Omniglot", "ImageNet", "ImageFolder")
LM_DATASETS = ("PennTreebank", "WikiText2", "WikiText103")

# Defaults mirroring the reference's config.yml (src/config.yml:1-55), minus
# torch-isms. ``device`` keeps its role as an execution hint ("tpu"/"cpu").
DEFAULT_CFG: Dict[str, Any] = {
    "control": {
        "fed": "1",
        "num_users": "100",
        "frac": "0.1",
        "data_split_mode": "iid",
        "model_split_mode": "fix",
        "model_mode": "a1",
        "norm": "bn",
        "scale": "1",
        "mask": "1",
    },
    "data_name": "CIFAR10",
    "subset": "label",
    "batch_size": {"train": 128, "test": 128},
    "shuffle": {"train": True, "test": False},
    "num_workers": 0,
    "model_name": "resnet18",
    "metric_name": {"train": ["Loss", "Accuracy"], "test": ["Loss", "Accuracy"]},
    "optimizer_name": "Adam",
    "lr": 3.0e-4,
    "momentum": 0.9,
    "weight_decay": 5.0e-4,
    "scheduler_name": "None",
    "step_size": 1,
    "milestones": [100, 150],
    "patience": 10,
    "threshold": 1.0e-3,
    "factor": 0.5,
    "min_lr": 1.0e-4,
    "init_seed": 0,
    "num_experiments": 1,
    "num_epochs": 200,
    "log_interval": 0.25,
    "device": "tpu",
    "world_size": 1,
    "resume_mode": 0,
    "save_format": "pdf",
    # ref writes TB scalars + info text every round unconditionally
    # (src/logger.py:57-84); here the writer is gated so headless runs stay
    # dependency-light, ON matching the reference when tensorboard is present
    "use_tensorboard": False,
    # TPU-native extras (no reference counterpart):
    # "masked" (one program, channel masks), "grouped" (rate-grouped dense
    # per-level programs on the mesh), "sliced" (host-orchestrated debug twin)
    "strategy": "masked",
    # "sharded": per-user train stacks live sharded over the clients axis and
    # every client trains on the device owning its shard (device memory scales
    # as U/n_devices); "replicated": all shards on every device.
    "data_placement": "replicated",
    # fuse the train-time masked BN into a Pallas TPU kernel (ops/pallas_norm.py)
    "pallas_norm": False,
    # conv lowering: None/"direct" = lax.conv (vmapped per-client kernels
    # become grouped convs); "im2col" = patch-extraction + batched matmul,
    # which keeps the client-vmapped hot path on dense MXU ops (ops/layers.py)
    "conv_impl": None,
    # cohort chunking (ISSUE 28): how many client slots of a device train AT
    # ONCE inside the round program.  None (default) = all of them, one vmap
    # over the whole cohort -- every program byte-identical to the unchunked
    # engines.  An int c trains c slots at a time: a lax.scan over slots/c
    # chunks carries the aggregate's sums and counts, so the round holds c
    # (not slots) copies of the masked model, its momentum and its gradients.
    # The round's result is the unchunked round's (same per-slot streams,
    # same single psum); a model whose one client is a third of the chip runs
    # at c = 1.  Masked engine only; c must divide the slots a device holds.
    "round_chunk": None,
    # lax.scan unroll factor for the local-step loop (1 = no unrolling);
    # latency-bound rounds can gain from fewer loop trips (not measured)
    "scan_unroll": 1,
    "param_dtype": "float32",
    "compute_dtype": "float32",  # set "bfloat16" to run matmuls/convs in bf16
    "mesh": {"clients": 0, "data": 1},  # 0 => use all available devices
    "data_dir": "./data",
    "output_dir": "./output",
    "synthetic": False,  # force synthetic data (offline/testing)
    "client_failure_rate": 0.0,  # per-round client crash probability (fault injection)
    "eval_interval": 1,  # rounds between sBN+eval passes (1 = reference parity)
    # async round pipelining: per-round train-metric sums stay on device and
    # are fetched every K rounds (parallel/staging.py MetricsPipeline), so
    # round t+1 dispatches while round t's sums transfer; eval boundaries
    # flush.  1 = synchronous fetch (reference parity).  K>1 logs train
    # metrics in K-round batches and a mid-batch checkpoint omits the not-
    # yet-fetched rounds from logger history (a perf knob, not a semantics
    # one).  With superstep_rounds>1 the legal values are 1 and
    # superstep_rounds: a larger batch would defer each superstep's eval
    # metrics past its checkpoint and silently disable best-checkpoint
    # tracking -- the driver fails loudly instead (ISSUE 6 satellite).
    "metrics_fetch_every": 1,
    # fused multi-round superstep: compile lax.scan over K federated rounds
    # into ONE jitted/donated program (parallel round_engine/grouped
    # train_superstep) -- per-round sampling, dynamic rate re-roll, failure
    # injection, the LR schedule AND the sBN+eval cadence all run in-jit
    # (eval rounds fire inside the scan on a static mask; eval_interval no
    # longer clamps K), metrics -- train and eval -- accumulate on device
    # and cross to the host once per superstep.  1 = one program per round
    # (host-loop eval, reference parity).  K>1 requires a mesh-native
    # strategy and metrics_fetch_every in {1} or multiples of K (whole
    # supersteps defer); ReduceLROnPlateau works when eval_interval % K == 0
    # (LR rides as a per-superstep scalar, stepped on the fused eval metrics
    # at superstep boundaries) and metrics_fetch_every <= K.  Checkpoints/
    # resume land on superstep boundaries; best-copy pivots on the LAST eval
    # of each superstep (intermediate evals log + feed Plateau but their
    # params are consumed inside the scan).  Under the masked engine with
    # replicated placement the per-round active set is sampled in-jit from
    # the jax key stream (fed.core.round_users) -- NOT the drivers' numpy
    # permutation stream used at superstep_rounds=1.
    "superstep_rounds": 1,
    # streaming million-user client store (ISSUE 6, parallel/staging.py
    # ClientStore + CohortStager): "eager" densifies the whole population
    # into [num_users, ...] stacks staged up front (the reference layout --
    # host/device memory scales with the population); "stream" keeps the
    # population as an O(1)-per-user metadata index and materialises only
    # each superstep's sampled cohort, committed via a double-buffered
    # device_put pipeline -- memory scales with active_clients and
    # superstep N+1's cohort stages while superstep N computes.  Streamed
    # supersteps are bit-identical to eager ones at matched seeds.  Needs a
    # mesh-native strategy; with superstep_rounds=1 the driver still runs
    # the (k=1) superstep path so rounds stay one-dispatch.
    "client_store": "eager",
    # streaming prefetch: True overlaps superstep N+1's cohort staging with
    # superstep N's compute (depth-1 double buffering); False forces
    # SYNCHRONOUS staging -- the loud fallback for samplers whose next
    # cohort depends on round-N outputs (the driver warns once).
    "stream_prefetch": True,
    # streaming prefetch depth (ISSUE 8 satellite): how many upcoming
    # supersteps' cohorts may be staged ahead of the in-flight one.  The
    # CohortStager ring holds depth+1 slots and fences each slot on its
    # previous private copy, so deeper pipelines stay corruption-safe; 1 =
    # the PR 6 double buffer.  Depth > 1 pays off once per-superstep
    # compute shrinks below the host gather time (real-TPU regime).
    "stream_prefetch_depth": 1,
    # wire codec (ISSUE 8, heterofl_tpu/compress/): compress the client
    # update INSIDE the fused round -- quantise -> ONE global psum ->
    # dequantise, preserving the one-global-psum invariant.  "dense"
    # (default) keeps today's f32 aggregation bit for bit; "int8" =
    # per-leaf stochastic-rounding quantisation with int32 lane-packed
    # accumulation (25% of dense bytes); "signsgd" = 1-bit signs with a
    # per-leaf scale (~19%); "topk" = rotating-block sparsification riding
    # the flat width-mask layout (25%).  Lossy codecs carry an
    # error-feedback residual in the scan state (donated, checkpointed),
    # have explicit tolerance contracts instead of the dense bitwise ones
    # (tests/test_compress.py), and need the fused superstep on the
    # grouped/sliced strategies.
    "wire_codec": "dense",
    # error feedback (ISSUE 8): re-inject each round's compression error
    # into the next round's payload (the residual carry).  True (default)
    # is the convergence-preserving setting; False drops the error -- the
    # A/B the convergence contract test pins.  Ignored by "dense".
    "error_feedback": True,
    # client scheduler (ISSUE 9, heterofl_tpu/sched/): who trains, for how
    # long, and when their update lands.  None (default) = lockstep -- the
    # paper's semantics, bit-identical to the pre-scheduler engines (zero
    # new program arguments).  A dict selects scenario mechanisms, all
    # running inside the fused K-round scan:
    #   {"kind": "uniform"|"trace"|"markov",  # availability schedule
    #    "trace": [[0/1,...],...],    # kind='trace': [rounds, num_users]
    #    "markov": {"p_on": .5, "p_off": .2, "length": 64, "seed": 0},
    #    "deadline": {"min_frac": 0.25},  # straggler local-step truncation
    #    "aggregation": "sync"|"buffered",  # buffered-async (staleness) or
    #    "staleness": 0.5}                  # its mixing coefficient alpha
    # Availability slots that cannot fill surface as -1 (padding) ids --
    # partial participation, not resampling.  Trace/markov schedules are
    # replayable from the config/seed, so checkpoint resume reproduces
    # identical cohorts and streaming prefetch keeps overlapping.  The
    # deadline and buffered modes have explicit contracts (superstep ==
    # sequential with the staleness buffer bit-for-bit; accuracy vs
    # lockstep recorded in MEASUREMENTS.md) instead of the dense bitwise
    # ones; buffered cannot combine with a lossy wire_codec (both add a
    # scan carry) and scenario schedules need a mesh-native strategy.
    "schedule": None,
    # population sampler (ISSUE 11, heterofl_tpu/fed/sampling.py): how the
    # per-round active cohort is drawn from THE one sampling stream
    # (fed.core.round_users).  "prp" (default) draws round r's cohort as
    # the image of [0, num_active) under a keyed pseudorandom-permutation
    # index map (variable-round Feistel + cycle-walking, exact bijection
    # for arbitrary num_users) -- O(active) work, no [num_users] buffer,
    # traceable in-jit; availability rows filter via an O(active x
    # overdraw) draw-then-filter walk with bounded spill to -1 padding.
    # "perm" is the legacy full jax.random.permutation(num_users) draw,
    # bit-for-bit identical to the pre-ISSUE-11 stream (parity tests, old
    # trajectory reproduction).  The two are different streams: switching
    # re-baselines every seeded trajectory.
    "sampler": "prp",
    # schedule commitment (ISSUE 11): None (default) = stateless sampler,
    # the schedule is a pure function of the key stream and streaming
    # prefetch is unconstrained.  An int >= 0 turns on commitment:
    # superstep N+1's cohort is drawn from superstep N-sample_horizon's
    # FETCHED state (fed.sampling.ScheduleCommitment gates the prefetch
    # queue), so an output-dependent sampler keeps the PR 6 staging
    # overlap (horizon 1) instead of forcing stream_prefetch=False.  For
    # the stateless perm/prp samplers the committed schedule is
    # bit-identical to the immediate one (contract-tested).
    "sample_horizon": None,
    # sampled/rolling eval cohort (ISSUE 9 satellite): with
    # client_store='stream', evaluate the per-user Local metrics on a
    # rolling N-user window instead of the whole population -- local eval
    # cost becomes O(eval_cohort), which is what makes eval_interval
    # affordable on a million-user run.  The window advances per eval
    # cadence (deterministic in the epoch, so resume is stable); sBN and
    # Global eval still cover their full sets.  None = whole-population
    # local eval (the pre-scheduler behaviour, warned past 1e5 users).
    "eval_cohort": None,
    # runtime telemetry (ISSUE 10, heterofl_tpu/obs/): "on" folds per-round
    # health probes -- global grad/update norm, per-level participation,
    # wire-codec residual norm, buffered staleness mass, a non-finite leaf
    # counter -- into the fused round programs' metrics pytree, computed
    # in-program from already-reduced values (ZERO new collectives; the
    # staticcheck telemetry variants pin the same one-psum wire budget).
    # "hist" (ISSUE 12) additionally folds the fixed-bucket COHORT
    # histograms in (obs/hist.py: per-client loss, deadline step fraction,
    # level membership, buffered staleness magnitude) -- still zero new
    # collectives, audited at the same budgets.
    # "off" (default) builds bit-identical programs to the pre-obs engines.
    # Needs a mesh-native strategy; the grouped engine needs the fused
    # superstep (superstep_rounds > 1 or client_store='stream').
    "telemetry": "off",
    # population observatory ledger (ISSUE 12, obs/ledger.py): "on"
    # maintains a host-side per-client record -- participation count,
    # last-seen round, cumulative staleness, loss EMA, level history --
    # updated O(active) at each metrics fetch from the cohort uid rows of
    # THE one sampling stream, checkpointed/restored with the run, and
    # snapshotted to ledger.npz for `python -m heterofl_tpu.obs.report`.
    # Resident cost ~27 bytes/user (uint8..uint32 arrays); never touches
    # the compiled programs (telemetry-independent).  Needs a mesh-native
    # strategy and replicated/streaming placement (the sharded slot
    # packing drops the uid ordering the fold consumes).
    "ledger": "off",
    # experiment arms multiplexer (ISSUE 14, heterofl_tpu/multi/): batch E
    # sweep arms into ONE fused superstep program.  None (default) = single
    # trajectory, every program byte-identical to pre-arms.  An int E (or a
    # dict {"count": E, "seeds": [...], "lr_scales": [...]}) vmaps the
    # K-round scan over a leading arms axis: per-arm PRNG streams
    # (fed.core.arm_stream_keys; seed None = the base stream), per-arm LR
    # scales over the shared schedule shape, metrics/eval stacked [E, K,
    # ...], still EXACTLY one global psum per fused round (wire bytes and
    # FLOPs scale linearly in E -- staticcheck arms variants audit this by
    # equality).  Arm i of a batched run is bitwise-identical to a solo
    # arms=1 run with the same seed.  Structural knobs (strategy, codec,
    # placement, schedule kind) stay per-program; unsupported combos --
    # sliced strategy, per-level codec maps, buffered aggregation, the
    # streaming store, grouped 'slices' placement, telemetry with grouped
    # -- refuse loudly.  python -m heterofl_tpu.multi.sweep partitions a
    # grid spec into arm batches x structural launches.
    "arms": None,
    # watchdog knobs (telemetry='on' enables it at warn defaults): a dict
    # {"action": "warn"|"abort"|"rollback"|"off", "spike_factor": 3.0,
    # "window": 8, "max_retries": 3, "backoff": 0.5} -- non-finite params
    # and loss-spikes-vs-rolling-median trip at fetch boundaries with a
    # loud warning ("warn"), a WatchdogError ("abort"), or an automatic
    # rollback (ISSUE 15): restore the newest finite-verifying checkpoint
    # generation, fold a retry salt into the round key stream (the
    # replayed superstep draws a FRESH cohort), retry up to max_retries
    # times with exponential backoff seconds, then escalate to abort.
    "watchdog": None,
    # in-program client-update quarantine (ISSUE 15 tentpole): a per-client
    # finiteness (+ optional update-norm) gate computed inside the fused
    # round from values each device already holds, folded into BOTH the
    # sums and the counts BEFORE the single global psum -- a NaN-poisoned
    # (or norm-exploded) client becomes a zero-count participant and the
    # globals never see its update.  "off" (default) keeps every program
    # bit-identical to the pre-quarantine engines; "on" gates on
    # finiteness only (bit-identical outputs when every update is clean);
    # a dict {"max_norm": R} additionally quarantines updates whose
    # masked L2 norm exceeds R.  The quarantined-client count rides the
    # metrics pytree as the obs_quarantine probe (zero new collectives,
    # same one-psum/wire budgets -- staticcheck quarantine variants).
    "quarantine": "off",
    # checkpoint generations (ISSUE 15): how many rotated checkpoint
    # generations to retain ({tag}_checkpoint.pkl, .g1, .g2, ...).  Every
    # write is fsync-before-rename with a SHA-256 content checksum;
    # resume/rollback fall back generation-by-generation to the newest
    # verifying blob.
    "checkpoint_keep": 3,
    # chaos fault injection (ISSUE 15, heterofl_tpu/chaos/): a list of
    # [round, uid] pairs whose client updates are NaN-poisoned IN-PROGRAM
    # after local training, before aggregation -- the deterministic
    # poisoned-client model the chaos drill and the quarantine/rollback
    # tests exercise.  None (default) leaves every program untouched.
    "chaos_poison": None,
    # run tracing (obs/trace.py): a directory to write a Chrome-trace-event
    # trace.json (set-up and compile spans from construction on, PhaseTimer
    # phases, driver events, jax.profiler annotations; open in Perfetto)
    # and a schema'd events.jsonl per run.
    # None = no tracing.  Independent of the probes (host-side only).
    "trace_dir": None,
    "profile_dir": None,  # write a jax.profiler trace of round 2 here
    "synthetic_sizes": None,  # {"train": n, "test": n} for synthetic data
    # Applied LAST by process_control: per-key overrides of any derived field
    # (dict values merge shallowly). E.g. {"num_epochs": {"global": 2},
    # "conv": {"hidden_size": [8, 16]}} -- used by tests and bench harnesses.
    "override": {},
}


def default_cfg() -> Dict[str, Any]:
    return copy.deepcopy(DEFAULT_CFG)


def parse_control_name(control_name: str) -> Dict[str, str]:
    """Split an underscore-separated control string into the 9 control fields.

    Mirrors ``src/train_classifier_fed.py:27-29``.
    """
    if control_name in (None, "None", ""):
        return {}
    parts = control_name.split("_")
    if len(parts) != len(CONTROL_KEYS):
        raise ValueError(
            f"control string must have {len(CONTROL_KEYS)} fields "
            f"{CONTROL_KEYS}, got {len(parts)}: {control_name!r}"
        )
    return dict(zip(CONTROL_KEYS, parts))


def control_name_of(control: Dict[str, str]) -> str:
    """Inverse of :func:`parse_control_name` (ref train_classifier_fed.py:30).

    Joins in canonical ``CONTROL_KEYS`` order (not dict insertion order) so a
    reordered dict still produces the canonical tag."""
    return "_".join(control[k] for k in CONTROL_KEYS)


def make_model_tag(seed: int, cfg: Dict[str, Any]) -> str:
    """Experiment tag keying checkpoints/results (ref train_classifier_fed.py:41-42)."""
    parts = [str(seed), cfg["data_name"], cfg.get("subset", ""), cfg["model_name"], cfg["control_name"]]
    return "_".join(x for x in parts if x)


def _fix_rate_vector(mode_rate: List[float], proportion: List[int], num_users: int) -> List[float]:
    """Static per-user rate assignment for ``fix`` mode.

    Exact parity with src/utils.py:134-144: each level gets
    ``num_users // sum(proportion) * proportion_i`` users in level order, and
    any remainder is filled with the *last* (smallest) level's rate.
    """
    if num_users < sum(proportion):
        raise ValueError(
            f"fix mode needs num_users >= sum of proportions: {num_users} users "
            f"< {sum(proportion)} (the reference crashes with an opaque "
            f"IndexError here); reduce the number of levels or add users")
    num_users_proportion = num_users // sum(proportion)
    model_rate: List[float] = []
    for i in range(len(mode_rate)):
        model_rate += list(np.repeat(mode_rate[i], num_users_proportion * proportion[i]))
    model_rate = model_rate + [model_rate[-1] for _ in range(num_users - len(model_rate))]
    return [float(r) for r in model_rate]


def process_control(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """Expand ``cfg['control']`` into every derived hyperparameter.

    Parity with ``src/utils.py:113-215``. Returns a new cfg dict (input is not
    mutated). Raises ``ValueError`` on invalid modes, like the reference.
    """
    cfg = copy.deepcopy(cfg)
    ctl = cfg["control"]
    cfg["control_name"] = control_name_of(ctl)
    cfg["model_split_rate"] = dict(MODEL_SPLIT_RATE)
    cfg["fed"] = int(ctl["fed"])
    cfg["num_users"] = int(ctl["num_users"])
    cfg["frac"] = float(ctl["frac"])
    cfg["data_split_mode"] = ctl["data_split_mode"]
    cfg["model_split_mode"] = ctl["model_split_mode"]
    cfg["model_mode"] = ctl["model_mode"]
    cfg["norm"] = ctl["norm"]
    cfg["scale"] = bool(int(ctl["scale"]))
    cfg["mask"] = bool(int(ctl["mask"]))
    cfg["global_model_mode"] = cfg["model_mode"][0]
    cfg["global_model_rate"] = cfg["model_split_rate"][cfg["global_model_mode"]]
    model_mode = cfg["model_mode"].split("-")
    mode_rate, proportion = [], []
    for m in model_mode:
        mode_rate.append(cfg["model_split_rate"][m[0]])
        proportion.append(int(m[1:]))
    if cfg["model_split_mode"] == "dynamic":
        cfg["model_rate"] = mode_rate
        cfg["proportion"] = (np.array(proportion) / sum(proportion)).tolist()
    elif cfg["model_split_mode"] == "fix":
        cfg["model_rate"] = _fix_rate_vector(mode_rate, proportion, cfg["num_users"])
    else:
        raise ValueError("Not valid model split mode")
    # Architecture tables (ref src/utils.py:147-149).
    cfg["conv"] = {"hidden_size": [64, 128, 256, 512]}
    cfg["resnet"] = {"hidden_size": [64, 128, 256, 512]}
    cfg["transformer"] = {
        "embedding_size": 256,
        "num_heads": 8,
        "hidden_size": 512,
        "num_layers": 4,
        "dropout": 0.2,
    }
    for family, shape in DECODER_FAMILIES.items():
        cfg[family] = copy.deepcopy(shape)
    # Per-dataset hyperparameters (ref src/utils.py:150-212).
    data_name = cfg["data_name"]
    split = cfg["data_split_mode"]
    if data_name in ("MNIST", "FashionMNIST", "EMNIST", "Omniglot"):
        cfg["data_shape"] = [105, 105, 1] if data_name == "Omniglot" else [28, 28, 1]  # NHWC
        cfg["optimizer_name"] = "SGD"
        cfg["lr"] = 1e-2
        cfg["momentum"] = 0.9
        cfg["weight_decay"] = 5e-4
        cfg["scheduler_name"] = "MultiStepLR"
        cfg["factor"] = 0.1
        if split == "iid":
            cfg["num_epochs"] = {"global": 200, "local": 5}
            cfg["batch_size"] = {"train": 10, "test": 50}
            cfg["milestones"] = [100]
        elif "non-iid" in split:
            cfg["num_epochs"] = {"global": 400, "local": 5}
            cfg["batch_size"] = {"train": 10, "test": 50}
            cfg["milestones"] = [200]
        elif split == "none":
            cfg["num_epochs"] = 200
            cfg["batch_size"] = {"train": 100, "test": 500}
            cfg["milestones"] = [100]
        else:
            raise ValueError("Not valid data_split_mode")
    elif data_name in ("ImageNet", "ImageFolder"):
        # shape is provisional; process_dataset overwrites it from the loaded
        # tree (folder datasets have data-defined geometry)
        cfg["data_shape"] = [224, 224, 3]
        cfg["optimizer_name"] = "SGD"
        cfg["lr"] = 1e-1
        cfg["momentum"] = 0.9
        cfg["weight_decay"] = 5e-4
        cfg["scheduler_name"] = "MultiStepLR"
        cfg["factor"] = 0.1
        if split == "iid" or "non-iid" in split:
            cfg["num_epochs"] = {"global": 400, "local": 5}
            cfg["batch_size"] = {"train": 10, "test": 50}
            cfg["milestones"] = [150, 250]
        elif split == "none":
            cfg["num_epochs"] = 400
            cfg["batch_size"] = {"train": 100, "test": 500}
            cfg["milestones"] = [150, 250]
        else:
            raise ValueError("Not valid data_split_mode")
    elif data_name in ("CIFAR10", "CIFAR100"):
        cfg["data_shape"] = [32, 32, 3]
        cfg["optimizer_name"] = "SGD"
        cfg["lr"] = 1e-1
        cfg["momentum"] = 0.9
        cfg["weight_decay"] = 5e-4
        cfg["scheduler_name"] = "MultiStepLR"
        cfg["factor"] = 0.1
        if split == "iid":
            cfg["num_epochs"] = {"global": 400, "local": 5}
            cfg["batch_size"] = {"train": 10, "test": 50}
            cfg["milestones"] = [150, 250]
        elif "non-iid" in split:
            cfg["num_epochs"] = {"global": 800, "local": 5}
            cfg["batch_size"] = {"train": 10, "test": 50}
            cfg["milestones"] = [300, 500]
        elif split == "none":
            cfg["num_epochs"] = 400
            cfg["batch_size"] = {"train": 100, "test": 500}
            cfg["milestones"] = [150, 250]
        else:
            raise ValueError("Not valid data_split_mode")
    elif data_name in ("PennTreebank", "WikiText2", "WikiText103"):
        cfg["optimizer_name"] = "SGD"
        cfg["lr"] = 1e-1
        cfg["momentum"] = 0.9
        cfg["weight_decay"] = 5e-4
        cfg["scheduler_name"] = "MultiStepLR"
        cfg["factor"] = 0.1
        cfg["bptt"] = 64
        cfg["mask_rate"] = 0.15
        if split == "iid":
            cfg["num_epochs"] = {"global": 200, "local": 1}
            cfg["batch_size"] = {"train": 100, "test": 10}
            cfg["milestones"] = [50, 100]
        elif split == "none":
            cfg["num_epochs"] = 100
            cfg["batch_size"] = {"train": 100, "test": 100}
            cfg["milestones"] = [25, 50]
        else:
            raise ValueError("Not valid data_split_mode")
    else:
        raise ValueError("Not valid dataset")
    for k, v in (cfg.get("override") or {}).items():
        if isinstance(v, dict) and isinstance(cfg.get(k), dict):
            cfg[k] = {**cfg[k], **v}
        else:
            cfg[k] = v
    # stale-config lint (ISSUE 8/18): unknown knob values AND cross-axis
    # conflicts fail HERE, at config validation, with the PR 6
    # loud-ValueError convention -- never as a silent fallback or a
    # mid-run refusal.  The chain below is THE canonical validator order;
    # staticcheck's config-lattice pass replays it point by point, so a
    # combination no validator refuses must be audited-green.
    for _name, fn in validator_chain():
        fn(cfg)
    return cfg


def validator_chain():
    """The canonical ``(name, resolve_*)`` validator sequence, in the order
    ``process_control`` applies it (ISSUE 18).  Axis validators run first
    (each owning its knob's domain), then the subsystem validators that
    additionally own that subsystem's cross-axis conflicts.  staticcheck's
    lattice pass (``staticcheck/lattice.py``) invokes exactly this chain to
    prove every refused config point raises from exactly one validator at
    config-resolution time -- keep additions HERE, never as driver-only
    checks (the lattice classifies a mid-run-only refusal as a finding).

    Every validator is jax-free and takes the full cfg dict; subsystem
    packages stay import-light so this chain never boots a backend."""
    from .chaos import resolve_poison_cfg
    from .compress import resolve_codec_cfg
    from .fed.sampling import resolve_sampler_cfg
    from .multi import resolve_arms_cfg
    from .obs import (resolve_ledger_cfg, resolve_quarantine_cfg,
                      resolve_telemetry_cfg)
    from .sched import resolve_schedule_cfg

    return [
        ("resolve_strategy_cfg", resolve_strategy_cfg),
        ("resolve_chunk_cfg", resolve_chunk_cfg),
        ("resolve_placement_cfg", resolve_placement_cfg),
        ("resolve_store_cfg", resolve_store_cfg),
        ("resolve_superstep_cfg", resolve_superstep_cfg),
        ("resolve_codec_cfg", resolve_codec_cfg),
        ("resolve_prefetch_depth", resolve_prefetch_depth),
        ("resolve_sampler_cfg", resolve_sampler_cfg),
        ("resolve_schedule_cfg", resolve_schedule_cfg),
        ("resolve_eval_cohort", resolve_eval_cohort),
        ("resolve_telemetry_cfg", resolve_telemetry_cfg),
        ("resolve_ledger_cfg", resolve_ledger_cfg),
        ("resolve_quarantine_cfg", resolve_quarantine_cfg),
        ("resolve_checkpoint_keep", resolve_checkpoint_keep),
        ("resolve_poison_cfg", resolve_poison_cfg),
        ("resolve_arms_cfg", resolve_arms_cfg),
    ]


def resolve_strategy_cfg(cfg: Dict[str, Any]) -> str:
    """Validate ``cfg['strategy']`` and return it (ISSUE 18).  THE one
    validator of the engine axis: an unknown strategy fails at config
    resolution, never as a driver-construction error."""
    strategy = cfg.get("strategy", "masked") or "masked"
    if strategy not in STRATEGIES:
        raise ValueError(f"Not valid strategy: {strategy!r} "
                         f"(one of {STRATEGIES})")
    return strategy


def resolve_chunk_cfg(cfg: Dict[str, Any]):
    """Validate ``cfg['round_chunk']`` and return it (ISSUE 28): None (all
    slots at once) or an int >= 1.  THE one validator of the chunk axis; the
    masked engine owns the chunked round core, so the other engines refuse
    here instead of silently training the whole cohort at once."""
    chunk = cfg.get("round_chunk")
    if chunk is None:
        return None
    if not isinstance(chunk, int) or isinstance(chunk, bool) or chunk < 1:
        raise ValueError(f"Not valid round_chunk: {chunk!r} (None = the "
                         f"whole cohort at once, or an int >= 1 of slots "
                         f"trained at a time)")
    strategy = resolve_strategy_cfg(cfg)
    if strategy != "masked":
        raise ValueError(
            f"Not valid round_chunk={chunk} with strategy={strategy!r}: the "
            f"chunked cohort scan lives in the masked engine's round core; "
            f"the {strategy} engine would silently train every slot at once")
    return chunk


def resolve_placement_cfg(cfg: Dict[str, Any]):
    """Validate ``cfg['data_placement']`` / ``cfg['level_placement']`` and
    return ``(data_placement, level_placement)`` (ISSUE 18).  THE one
    validator of the placement axis, including its engine cross-checks:

    - ``grouped`` needs replicated data placement (a level's clients span
      the whole clients axis) -- promoted from the grouped constructor;
    - ``level_placement='slices'`` is the grouped engine's per-level
      device partition; the other engines have no level sub-meshes;
    - the ``sliced`` host twin takes neither placement knob -- previously
      both were silently ignored (exactly the silent fallback the lattice
      pass exists to refuse)."""
    strategy = resolve_strategy_cfg(cfg)
    dp = cfg.get("data_placement", "replicated") or "replicated"
    lp = cfg.get("level_placement", "span") or "span"
    if dp not in DATA_PLACEMENTS:
        raise ValueError(f"Not valid data_placement: {dp!r} "
                         f"(one of {DATA_PLACEMENTS})")
    if lp not in LEVEL_PLACEMENTS:
        raise ValueError(f"Not valid level_placement: {lp!r} "
                         f"(one of {LEVEL_PLACEMENTS})")
    if strategy == "grouped" and dp == "sharded":
        raise ValueError(
            "Not valid data_placement='sharded' with strategy='grouped': "
            "a level's clients span the whole clients axis, so the grouped "
            "engine packs slot schedules from the replicated store; use "
            "strategy='masked' for sharded placement")
    if lp == "slices" and strategy != "grouped":
        raise ValueError(
            f"Not valid level_placement='slices' with strategy="
            f"{strategy!r}: the slices partition assigns each rate level "
            f"its own clients-axis device rows, which only the grouped "
            f"engine's per-level dense programs consume")
    if strategy == "sliced" and dp != "replicated":
        raise ValueError(
            f"Not valid data_placement={dp!r} with strategy='sliced': the "
            f"host-orchestrated debug twin replays the reference loop and "
            f"ignores device placement -- the knob would silently no-op")
    return dp, lp


def resolve_store_cfg(cfg: Dict[str, Any]) -> str:
    """Validate ``cfg['client_store']`` and return it (ISSUE 18).  THE one
    validator of the store axis: unknown modes and the stream x sliced
    conflict (promoted from the driver) fail at config resolution."""
    strategy = resolve_strategy_cfg(cfg)
    store = cfg.get("client_store", "eager") or "eager"
    if store not in CLIENT_STORES:
        raise ValueError(f"Not valid client_store: {store!r} "
                         f"(one of {CLIENT_STORES})")
    if store == "stream" and strategy == "sliced":
        raise ValueError(
            "Not valid client_store='stream' with strategy='sliced': the "
            "cohort pipeline stages through the mesh-native engines' "
            "superstep programs ('masked' or 'grouped')")
    if store == "stream" and cfg.get("data_placement") == "sharded":
        raise ValueError(
            "Not valid data_placement='sharded' with client_store="
            "'stream': the streaming population stages per-superstep "
            "cohorts through its own placement path, so the sharded "
            "slot packing would silently no-op -- use replicated")
    return store


def resolve_superstep_cfg(cfg: Dict[str, Any]) -> int:
    """Validate ``cfg['superstep_rounds']`` and its cross-axis contracts,
    returning the round count K (ISSUE 18).  THE one validator of the pod
    axis; the ``metrics_fetch_every`` / Plateau / streaming interplays are
    promoted from the driver (``entry/common.py``), where they refused at
    construction -- same typed messages, now at config-resolution time."""
    raw = cfg.get("superstep_rounds", 1)
    if raw is None:
        raw = 1
    if not isinstance(raw, int) or isinstance(raw, bool) or raw < 1:
        raise ValueError(f"Not valid superstep_rounds: {raw!r} "
                         f"(an int >= 1)")
    K = raw
    strategy = resolve_strategy_cfg(cfg)
    store = resolve_store_cfg(cfg)
    fetch_every = int(cfg.get("metrics_fetch_every", 1) or 1)
    eval_iv = max(1, int(cfg.get("eval_interval", 1) or 1))
    if K > 1:
        if strategy == "sliced":
            raise ValueError(
                "Not valid superstep_rounds>1 with strategy='sliced': the "
                "fused superstep needs a mesh-native engine ('masked' or "
                "'grouped'); 'sliced' is the host-orchestrated debug twin")
        if fetch_every != 1 and fetch_every % K:
            raise ValueError(
                f"Not valid metrics_fetch_every={fetch_every} with "
                f"superstep_rounds={K}: a superstep fetches its metrics "
                f"exactly once per K rounds (use 1 for synchronous fetch "
                f"or exactly {K}; larger multiples would defer metrics "
                f"past the superstep's checkpoint)")
        if fetch_every > K:
            raise ValueError(
                f"Not valid metrics_fetch_every={fetch_every} with "
                f"superstep_rounds={K}: each superstep's eval metrics "
                f"would be deferred past its checkpoint, silently "
                f"disabling best-checkpoint tracking (pivot never fresh); "
                f"use 1 or {K}")
        if cfg.get("scheduler_name") == "ReduceLROnPlateau" and eval_iv % K:
            raise ValueError(
                f"Not valid scheduler_name='ReduceLROnPlateau' with "
                f"superstep_rounds={K} and eval_interval={eval_iv}: "
                f"Plateau needs eval boundaries on superstep boundaries "
                f"(eval_interval % superstep_rounds == 0) -- a "
                f"mid-superstep eval would require an LR step inside the "
                f"compiled scan")
    elif store == "stream" and fetch_every > 1:
        raise ValueError(
            f"Not valid metrics_fetch_every={fetch_every} with "
            f"client_store='stream' at superstep_rounds=1: streaming "
            f"routes through the (k=1) superstep path, whose "
            f"best-checkpoint pivot needs a synchronous fetch; use 1")
    return K


def resolve_prefetch_depth(cfg: Dict[str, Any]) -> int:
    """Validate ``cfg['stream_prefetch_depth']`` and return it (ISSUE 8
    satellite).  THE one validator: process_control applies it, and the
    engines/driver (often built directly from a cfg dict, bypassing
    process_control) re-apply it rather than coercing bad values to the
    default."""
    depth = cfg.get("stream_prefetch_depth", 1)
    if depth is None:
        return 1
    if not isinstance(depth, int) or isinstance(depth, bool) or depth < 1:
        raise ValueError(f"Not valid stream_prefetch_depth: {depth!r} "
                         f"(an int >= 1)")
    return depth


def resolve_checkpoint_keep(cfg: Dict[str, Any]) -> int:
    """Validate ``cfg['checkpoint_keep']`` and return it (ISSUE 15).  THE
    one validator: process_control applies it and the driver re-applies it
    -- a malformed value fails loudly at config time, never as a silent
    single-generation fallback mid-run.  Lives here (not in
    utils.checkpoint) to keep this module's jax-free import contract."""
    keep = cfg.get("checkpoint_keep", 3)
    if keep is None:
        return 3
    if not isinstance(keep, int) or isinstance(keep, bool) or keep < 1:
        raise ValueError(f"Not valid checkpoint_keep: {keep!r} (an int >= 1 "
                         f"checkpoint generations to retain)")
    return keep


def resolve_eval_cohort(cfg: Dict[str, Any]):
    """Validate ``cfg['eval_cohort']`` and return it (ISSUE 9 satellite).
    THE one validator: process_control applies it and the driver re-applies
    it (cross-field constraints -- streaming store, vision models -- live
    in the driver, which owns those facts)."""
    ec = cfg.get("eval_cohort")
    if ec is None:
        return None
    if not isinstance(ec, int) or isinstance(ec, bool) or ec < 1:
        raise ValueError(f"Not valid eval_cohort: {ec!r} (an int >= 1, the "
                         f"rolling Local-eval window size, or None for "
                         f"whole-population local eval)")
    users = cfg.get("num_users")
    if users is not None and ec > int(users):
        raise ValueError(f"Not valid eval_cohort: {ec} exceeds "
                         f"num_users={users} (drop eval_cohort for "
                         f"whole-population local eval)")
    # eval-cohort cross-checks (ISSUE 18): promoted from the driver.  This
    # validator OWNS the eval-cohort axis in the staticcheck lattice.
    if (cfg.get("client_store", "eager") or "eager") != "stream":
        raise ValueError(
            f"Not valid eval_cohort={ec} with client_store='eager': the "
            f"eager store already densifies the population, so its local "
            f"eval is O(num_users) either way -- eval_cohort needs "
            f"client_store='stream'")
    if cfg.get("model_name") in LM_MODEL_NAMES:
        raise ValueError(
            f"Not valid eval_cohort={ec} with model_name="
            f"{cfg.get('model_name')!r}: "
            f"eval_cohort samples the per-user Local eval, which only "
            f"vision experiments run (LM evaluates Global only)")
    return ec


def ceil_width(size: int, rate: float) -> int:
    """Active width of a sliced dimension: ``ceil(size * rate)`` (ref fed.py:47)."""
    return int(math.ceil(size * rate))


def scaled_hidden(hidden_size: List[int], model_rate: float) -> List[int]:
    """Per-layer widths of a sub-model (ref models/conv.py:77)."""
    return [ceil_width(x, model_rate) for x in hidden_size]
