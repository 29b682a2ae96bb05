"""Training launcher: ``python -m hetu_galvatron_tpu.cli.train_dist
<config.yaml> [key=value ...]``.

Capability parity with the reference launcher (models/gpt/train_dist.py:21-84):
load config -> initialize -> resolve model -> build hybrid-parallel plan ->
data iterators -> optimizer -> iteration loop with profiler/logging/
checkpoint hooks. One launcher serves every model family (the model zoo is
YAML, models/configs/*.yaml).
"""

from __future__ import annotations

import sys
import time
from typing import Any, Dict, Optional

import numpy as np


def _flight_dir_of(args):
    """THE flight-dump directory resolution (explicit flight_dir, else
    the metrics stream's directory) — shared by the crash-path recorder
    construction in train() and the elastic postmortem below, so both
    kinds of dump land in the same place."""
    import os as _os

    fdir = args.observability.flight_dir
    if fdir is None:
        fdir = _os.path.dirname(_os.path.abspath(
            args.observability.metrics_path or _os.path.join(
                args.logging.tensorboard_dir or ".", "metrics.jsonl")))
    return fdir


def _flight_dump_elastic(args, reason: str, live_world: int,
                         stored_world: int, kind: str):
    """Leave a flight-recorder postmortem for a terminal elastic failure
    (rejected re-plan or reshard error — the run exits 17; the dump is
    the operator's first artifact). Returns the dump path, or None when
    no dump directory is configured/derivable. Never raises (the
    recorder's own contract)."""
    if args.observability.flight_dir is None \
            and not args.observability.enabled:
        return None
    from hetu_galvatron_tpu.observability.recorder import FlightRecorder

    rec = FlightRecorder(registry=None, out_dir=_flight_dir_of(args),
                         capacity=args.observability.flight_events)
    rec.note("elastic_replan", reason=reason, live_world=live_world,
             stored_world=stored_world, ckpt_load=args.ckpt.load)
    return rec.dump(kind)


def tower_pairs_tiled(cfg, use_flash: bool) -> int:
    """The pairs the tower's cores compute, a sequence, block and head: the
    tiles the flash kernels' loops visit in the calls that are not causal,
    by the lengths and tiles those calls were built with
    (``flash_attention.TWO_WAY_CALLS``) and, for the calls over a sequence's
    patches, by the ids the tower hands them (static: the traffic's grids),
    which bound those loops; the XLA core makes the square of a sequence's
    patches."""
    from hetu_galvatron_tpu.models.tower import grids_of, image_of_patch
    from hetu_galvatron_tpu.ops.pallas.flash_attention import (
        TWO_WAY_CALLS,
        two_way_tiles,
    )

    if not use_flash:
        return sum(cfg.image_patches) ** 2
    images = image_of_patch(grids_of(cfg))
    return sum(
        two_way_tiles(S, Sk, bq, bk,
                      images if S == Sk == len(images) else None) * bq * bk
        for S, Sk, bq, bk in TWO_WAY_CALLS)


def train(args) -> Dict[str, Any]:
    from hetu_galvatron_tpu.observability.tracing import span

    # one-shot set-up spans (registry only: no profiler window is open
    # yet); with the first iteration's train/dispatch they tile the time
    # from here to the loop. The imports are timed where they run.
    with span("setup/imports"):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec

        from hetu_galvatron_tpu.core.profiler.runtime_profiler import (
            RuntimeProfiler,
            compiled_memory_bytes,
        )
        from hetu_galvatron_tpu.models.builder import init_causal_lm
        from hetu_galvatron_tpu.parallel.spmd import make_spmd_train_step
        from hetu_galvatron_tpu.runtime.checkpoint import (
            CheckpointCadence,
            clear_resume_pin,
            latest_checkpoint,
            load_latest_resilient,
            save_checkpoint,
            try_read_checkpoint_meta,
        )
        from hetu_galvatron_tpu.runtime.chaos import make_chaos
        from hetu_galvatron_tpu.runtime.dataloader import (
            get_train_valid_test_data_iterators,
            skip_batches,
        )
        from hetu_galvatron_tpu.runtime.hybrid_config import (
            get_hybrid_parallel_config,
        )
        from hetu_galvatron_tpu.runtime.initialize import initialize
        from hetu_galvatron_tpu.runtime.mesh import build_mesh
        from hetu_galvatron_tpu.runtime.optimizer import (
            HostSchedule,
            make_optimizer,
        )
        from hetu_galvatron_tpu.runtime.pipeline import PipelineEngine
        from hetu_galvatron_tpu.runtime.rerun_machine import (
            FaultDrill,
            RerunDataIterator,
            RerunStateMachine,
        )
        from hetu_galvatron_tpu.runtime.supervisor import PreemptionGuard
        from hetu_galvatron_tpu.utils.hf_config_adapter import (
            resolve_model_config,
        )

    with span("setup/runtime"):
        args = resolve_model_config(args)

        # goodput accounting (observability/goodput.py): wall-clock
        # partitioned into productive / recompile / save / resume-replay /
        # reshard / restart-lost; snapshots ride every checkpoint's
        # train_state, so the goodput/* gauges survive preemption with the
        # model state. Constructed before the elastic pre-pass so topology
        # changes bill their re-search + reshard wall into the new bucket.
        from hetu_galvatron_tpu.observability.goodput import GoodputTracker

        goodput = GoodputTracker()

        # ----- elastic pre-pass: detect a topology-changed resume -----------
        # BEFORE initialize/plan construction: the preserved CLI plan (or the
        # checkpoint's JSON plan) describes the OLD world and may not even
        # validate on the new one. When the live world differs from the
        # checkpoint's recorded world_size, re-search a plan for the new
        # topology (cli/search_dist.py internals), gate it through the memory
        # doctor's HBM budget, and remember to reshard instead of plain-load.
        elastic = None
        if args.ckpt.load:
            from hetu_galvatron_tpu.runtime.initialize import (
                visible_world_size,
            )

            live_world = visible_world_size(args)
            ckdir0 = latest_checkpoint(args.ckpt.load)
            stored_plan = (try_read_checkpoint_meta(ckdir0)[0]
                           .get("hybrid_parallel_config") if ckdir0 else None)
            stored_world = (stored_plan or {}).get("world_size")
            if stored_world and int(stored_world) != live_world:
                from hetu_galvatron_tpu.cli.search_dist import replan_for_world
                from hetu_galvatron_tpu.runtime.rerun_machine import (
                    EXIT_CODE_FAILED_ON_RESULT_VALIDATION,
                )

                print(f"elastic resume: {ckdir0} was committed by a "
                      f"{stored_world}-device world; live world is "
                      f"{live_world} — re-planning", flush=True)
                with goodput.measure("reshard"):
                    reason = replan_for_world(args, live_world, stored_plan)
                if reason is not None:
                    # terminal by contract: an infeasible or OOM-rejected
                    # target plan reproduces on every restart — exit 17 with
                    # a flight-recorder postmortem, never a restart loop
                    print(f"elastic resume failed terminally: {reason}",
                          flush=True)
                    dump = _flight_dump_elastic(args, reason, live_world,
                                                stored_world,
                                                "elastic_plan_rejected")
                    return {"losses": [], "val_losses": [], "test_loss": None,
                            "iter_ms": 0.0, "rerun": None,
                            "goodput": {"totals": dict(goodput.totals),
                                        "frac": goodput.goodput(),
                                        "restarts_survived":
                                            goodput.restarts_survived},
                            "flight_dumps": [dump] if dump else [],
                            "exit_code": EXIT_CODE_FAILED_ON_RESULT_VALIDATION}
                elastic = {"ckdir": ckdir0, "stored_world": int(stored_world)}

        state = initialize(args)
        world = state.world_size
        hpc = get_hybrid_parallel_config(args, world)
        state.log(f"parallel plan: {hpc.describe()}")
        if hpc.ignored_plan_keys:
            state.log("plan keys " + ", ".join(hpc.ignored_plan_keys)
                      + " are ignored: gradients are reduced over dp by "
                      "XLA's partitioner (the flat reduction)")

        cfg = args.model
        # which attention core each layer runs — decided ONCE from the plan
        # and the run's own devices (runtime/mesh.py), logged, and returned in
        # the result so a run on the XLA core can never pass for a kernel run
        from collections import Counter

        from hetu_galvatron_tpu.observability.registry import get_registry
        from hetu_galvatron_tpu.runtime.mesh import (
            attention_core,
            flash_kernel_runs,
        )

        use_flash = flash_kernel_runs(cfg.use_flash_attn, state.devices)
        # a block that does not attend reports its own operator, so that
        # "every core is flash" stays a statement about the blocks that do
        from hetu_galvatron_tpu.models.modules import MIXERS

        from hetu_galvatron_tpu.ops.pallas.flash_attention import (
            LAYOUT_CALLS,
            TWO_WAY_CALLS,
            WINDOWED_CALLS,
            band_tiles,
            effective_window,
        )

        kinds = cfg.block_kinds(len(hpc.layers))
        # a window block's core carries its window, ``flash[w512]``; a
        # window no shorter than the sequence is none
        window = effective_window(cfg.sliding_window, cfg.seq_length)
        attention_cores = [
            MIXERS[mixer].logged or attention_core(
                s.cp_size > 1, bool(s.sp and s.tp_size > 1), use_flash)
            + (f"[w{window}]" if window and mixer == "sliding_attention"
               else "")
            for s, (mixer, _) in zip(hpc.layers, kinds)
            # a feed-forward block of a one-branch stack has no core
            if mixer is not None]
        tower_report: Dict[str, Any] = {}
        if cfg.tower_layers and cfg.image_grids:
            # a tower of image patches in front of the decoder: its blocks
            # attend on the first block's plan's core and come first in the
            # list; what a step holds of images is fixed by the traffic's
            # grids (facts of the run: tower/* gauges)
            from hetu_galvatron_tpu.models.tower import grids_of, pairs_masked
            from hetu_galvatron_tpu.runtime.dataloader import image_layout

            attention_cores = ([attention_core(False, False, use_flash)]
                               * cfg.tower_layers + attention_cores)
            rows = hpc.global_bsz
            tower_report = {
                "blocks": cfg.tower_layers,
                "patches": rows * sum(cfg.image_patches),
                "image_positions": rows * cfg.image_positions,
                # the positions whose label is no image position
                "marked_positions": rows * int(cfg.seq_length - image_layout(
                    cfg, args.data.image_text_spans)[1:].sum()),
                # the (query, key) pairs the mask leaves, a block and head
                # (what the cores' tiles cover is read off the calls once
                # the step is built: pairs_tiled, in the step report)
                "pairs_masked": rows * pairs_masked(grids_of(cfg))}
            for k, v in tower_report.items():
                get_registry().gauge(f"tower/{k}").set(v)
        state.log("attention cores: " + ", ".join(
            f"{n} x {core}" for core, n in Counter(attention_cores).items())
            + (" (the first {blocks} the tower's: {patches} patches, "
               "{image_positions} image positions and {marked_positions} "
               "marked positions a step, {pairs_masked} pairs inside "
               "images)".format(**tower_report)
               if tower_report else ""))
        if "sliding_attention" in (cfg.layer_types or ()):
            # each attending block's window (0 = the causal span) and query
            # heads, as the configuration gives them
            for i, (m, _) in enumerate(kinds):
                if m not in ("full_attention", "sliding_attention"):
                    continue
                w = window if m == "sliding_attention" else None
                get_registry().gauge("attn/window", layer=f"layer{i}").set(
                    w or 0)
                get_registry().gauge("attn/heads", layer=f"layer{i}").set(
                    cfg.block_heads(i))
        # the flash calls this run's step is built of are recorded from here
        # on: those with a window, and all of them by the layout the kernels
        # index (the step report reads them)
        WINDOWED_CALLS.clear()
        LAYOUT_CALLS.clear()
        TWO_WAY_CALLS.clear()
        # how many blocks of each mixer and feed-forward kind the step holds
        blocks = {}
        for (m, ff), n in Counter(kinds).items():
            # '-': a block of one branch has no mixer, or no feed-forward
            m, ff = m or "-", ff or "-"
            blocks[f"{m}/{ff}"] = n
            get_registry().gauge("step/blocks", mixer=m, ff=ff).set(n)
        if cfg.one_branch_blocks:
            get_registry().gauge("blocks/mixer_only").set(
                sum(ff is None for _, ff in kinds))
            get_registry().gauge("blocks/ff_only").set(
                sum(m is None for m, _ in kinds))
        # expert blocks across chips: which take the exchange, what a chip
        # sends around them a step (from the shapes), and by name any sorted
        # dispatcher under ep > 1 that the exchange does not serve
        from hetu_galvatron_tpu.analysis import eligibility
        from hetu_galvatron_tpu.models.moe import (
            exchange_bytes,
            held_range,
            layer_body,
            overflow_rows,
            short_rows,
        )

        ep_report: Dict[str, Any] = {}
        exchanged = [
            s for s, (_, ff) in zip(hpc.layers,
                                    cfg.block_kinds(len(hpc.layers)))
            if ff == "experts" and eligibility.takes_exchange(
                cfg, s, hpc.pp_deg)] if cfg.num_experts else []
        if exchanged:
            ep_report = {
                "axes": exchanged[0].ep_size, "blocks": len(exchanged),
                "exchange_bytes_per_step": sum(exchange_bytes(
                    hpc.global_bsz // s.dp_size * cfg.seq_length,
                    cfg.hidden_size, cfg.moe_topk, s.ep_size,
                    4 if args.parallel.mixed_precision == "fp32" else 2,
                    3 if s.checkpoint else 2) for s in exchanged)}
            # a chip's expert layer walks the group's routes of a
            # microbatch: the first chunk that always runs and one counted
            # pass behind it, in rows (``moe._held_dispatch``)
            first = exchanged[0]
            slots = (hpc.global_bsz // max(hpc.chunks, 1) // first.dp_size
                     * cfg.seq_length * first.ep_size * cfg.moe_topk)
            held = cfg.num_experts // first.ep_size
            ep_report["first_chunk_rows"] = short_rows(
                slots, held, cfg.num_experts, cfg.moe_capacity_factor)
            ep_report["pass_rows"] = overflow_rows(
                slots, held, cfg.num_experts)
            state.log("expert exchange: ep/axes {axes} over {blocks} expert "
                      "blocks, ep/exchange_bytes_per_step "
                      "{exchange_bytes_per_step}, ep/first_chunk_rows "
                      "{first_chunk_rows}, ep/pass_rows {pass_rows}".format(
                          **ep_report))
            for k in ("axes", "exchange_bytes_per_step", "first_chunk_rows",
                      "pass_rows"):
                get_registry().gauge(f"ep/{k}").set(ep_report[k])
        # which body each sorted expert layer's dispatcher compiles to, from
        # the routes of a microbatch it walks (an exchanged layer: its ep
        # group's, for a chip's share of the experts)
        micro_slots = (hpc.global_bsz // max(hpc.chunks, 1) * cfg.seq_length
                       * cfg.moe_topk)
        expert_bodies = {}
        for i, (s, (_, ff)) in enumerate(zip(hpc.layers, kinds)):
            if ff == "experts" and cfg.moe_dispatcher == "dropless":
                ep = s.ep_size if s in exchanged else 1
                expert_bodies[f"layer{i}"] = layer_body(
                    micro_slots // (s.dp_size if ep > 1 else 1) * ep,
                    held_range(cfg, ep)[0], cfg.num_experts,
                    cfg.moe_capacity_factor)
        unserved = eligibility.ep_plan_reason(cfg, hpc.layers, hpc.pp_deg)
        if unserved:
            state.log(f"expert exchange not taken: {unserved}")
        # what a state-space block carries: the chunks of a sequence and
        # the float32 state one sequence hands from chunk to chunk; the
        # groups of B and C its heads read (one: shared by all heads)
        if any(m == "mamba" for m, _ in kinds):
            get_registry().gauge("ssd/groups").set(cfg.mamba_n_groups)
        for i, (m, _) in enumerate(kinds):
            if m == "mamba":
                get_registry().gauge("ssd/chunks", layer=f"layer{i}").set(
                    -(-cfg.seq_length // cfg.mamba_chunk_size))
                get_registry().gauge("ssd/state_bytes", layer=f"layer{i}"
                                     ).set(4 * cfg.mamba_d_inner
                                           * cfg.mamba_d_state)

        # what a stack whose blocks read earlier blocks' values holds: its
        # blocks by kind and the bytes a step's microbatch keeps of the
        # memory and of the keys and values between the block that leaves
        # them and the blocks that take them
        shared_report: Dict[str, int] = {}
        if any(leaves or takes for leaves, takes in cfg.block_shares(
                len(hpc.layers))):
            rows = hpc.global_bsz // max(hpc.chunks, 1) * cfg.seq_length
            width = {"fp32": 4}.get(args.parallel.mixed_precision, 2)
            count = Counter(m for m, _ in kinds)
            shared_report = {
                "mamba1/blocks": count["mamba1"],
                "gmu/blocks": count["gmu"],
                "cross/blocks": count["cross_attention"],
                "shared/memory_bytes": rows * cfg.mamba1_d_inner * width
                * (count["gmu"] > 0),
                "shared/kv_bytes": rows * 2 * cfg.kv_heads * cfg.head_dim
                * width * (count["cross_attention"] > 0)}
            for name, v in shared_report.items():
                get_registry().gauge(name).set(v)
            state.log("shared values: " + ", ".join(
                f"{k} {v}" for k, v in shared_report.items()))

        # abstract init first: the plan's shardings are derived from SHAPES, so
        # no device materializes the unsharded tree before they exist (the
        # pp=1 path then initializes straight into its shards)
        init_key = jax.random.key(args.train.seed)
        axes_box: Dict[str, Any] = {}

        def init_params(key):
            p, axes_box["axes"] = init_causal_lm(key, cfg)
            return p

        params = jax.eval_shape(init_params, init_key)
        axes = axes_box["axes"]
        tx = make_optimizer(args.train)
        host_lr = HostSchedule(args.train)
        base_iter, valid_iter, test_iter = get_train_valid_test_data_iterators(
            args, global_batch_size=hpc.global_bsz, hpc=hpc)
        place_box: Dict[str, Any] = {}
        if cfg.tower_layers and cfg.image_grids:
            # pixels are megabytes a sequence where ids are kilobytes: they
            # go to the device from a thread of their own, a batch ahead, so
            # the copy runs under the previous step and train/h2d finds them
            # there (a batch drawn before the step's sharding is known goes
            # as it is). ``patch_grids`` says what the loader packed: held
            # here to the grids the step's tables were built from, and not
            # sent (the tower reads ``model.image_grids``)
            from hetu_galvatron_tpu.runtime.dataloader import one_ahead

            def placed(batches):
                for b in batches:
                    b = dict(b)
                    if b.pop("patch_grids")[0].tolist() != cfg.image_grids:
                        raise ValueError(
                            "the loader packed images of other grids than "
                            f"model.image_grids {cfg.image_grids}")
                    if "batch_shd" in place_box:
                        b["patches"] = jax.device_put(
                            b["patches"], place_box["batch_shd"])
                    yield b

            base_iter = one_ahead(placed(base_iter))
        data_iter = RerunDataIterator(base_iter)
        # unified telemetry (observability/): configures the process-wide
        # registry with JSONL (+optional TensorBoard) sinks, so the profiler's
        # histograms, the rerun machine's counters, and the derived
        # throughput/MFU stats all land in one metrics stream
        telemetry = None
        # rank-gated like the profiler's printing and TraceCapture: on a
        # multi-host pod only process 0 writes the metrics stream (every
        # process appending to one shared-storage JSONL would interleave)
        if args.observability.enabled and jax.process_index() == 0:
            from hetu_galvatron_tpu.observability.telemetry import (
                emit_plan_telemetry,
            )
            from hetu_galvatron_tpu.runtime.trainer import make_telemetry

            telemetry = make_telemetry(args, world_size=world,
                                       global_batch_size=hpc.global_bsz)
            emit_plan_telemetry(
                telemetry.registry, hpc, cfg,
                mixed_precision=args.parallel.mixed_precision != "fp32")
        # crash-forensics flight recorder (observability/recorder.py): dumps
        # flight_<ts>.json on crash / trapped signal / rerun halt. Directory:
        # observability.flight_dir, else (when telemetry owns a stream) the
        # metrics file's directory
        recorder = None
        if jax.process_index() == 0 and (telemetry is not None
                                         or args.observability.flight_dir):
            from hetu_galvatron_tpu.observability.recorder import (
                FlightRecorder,
            )

            recorder = FlightRecorder(
                registry=(telemetry.registry if telemetry is not None
                          else None),
                out_dir=_flight_dir_of(args),
                capacity=args.observability.flight_events)
            recorder.note("run_start", plan=hpc.describe(), world=world)
        profiler = RuntimeProfiler(args, world_size=world,
                                   rank=jax.process_index(),
                                   pass_rows=ep_report.get("pass_rows"))
        rerun = RerunStateMachine(args.rerun)
        # preemption guard + at-step-k fault drill (runtime/supervisor.py):
        # SIGTERM/SIGINT become a checkpoint-and-exit at the next step boundary
        guard = PreemptionGuard(enabled=args.supervisor.graceful_signals,
                                recorder=recorder)
        drill = FaultDrill(args.rerun)
        # chaos fault plan (runtime/chaos.py): step-targeted crashes/signals
        # plus mid-save and retry-seam faults, one-shot across process
        # restarts via marker files next to the checkpoints
        chaos = make_chaos(
            args,
            registry=(telemetry.registry if telemetry is not None else None),
            log=state.log)
        if chaos is not None:
            chaos.install()
            state.log(f"chaos: armed faults {chaos.pending()}")
    start_iter = 0

    # overlapped-TP collectives (tp_overlap.enable, ops/overlap.py):
    # resolve per-layer eligibility once from the plan, log every fallback
    # with its reason, and remember the overlapped layer set for the
    # tp/comm_hidden_frac gauge. The rings run under BOTH pipeline
    # schedule impls: per stage submesh on the host engine, and as
    # stage-stacked shard_maps inside the compiled engine's fused program.
    tp_overlap_on = args.tp_overlap.enable
    overlapped_layers: list = []
    if tp_overlap_on:
        from hetu_galvatron_tpu.analysis.eligibility import (
            plan_overlap_reasons,
        )

        reasons = plan_overlap_reasons(cfg, hpc)
        overlapped_layers = [i for i, r in reasons if r is None]
        for i, r in reasons:
            if r is not None:
                state.log(f"tp_overlap: layer {i} falls back to GSPMD "
                          f"collectives ({r})")
        if not overlapped_layers:
            state.log("tp_overlap.enable set but no layer is eligible; "
                      "running the GSPMD path")
            tp_overlap_on = False

    def finish_tp_overlap_setup(step_fn):
        """Once the engine choice has settled: emit the coverage gauge and
        wrap the step in the ``tp/overlap_step`` span."""
        if not tp_overlap_on:
            return step_fn
        state.log(f"tp_overlap: {len(overlapped_layers)}/{len(hpc.layers)} "
                  "layers run decomposed ring collective matmuls")
        if telemetry is not None:
            from hetu_galvatron_tpu.observability.telemetry import (
                plan_tp_overlap_hidden_frac,
            )

            telemetry.registry.gauge("tp/comm_hidden_frac").set(
                plan_tp_overlap_hidden_frac(
                    hpc, cfg, overlapped_layers,
                    mixed_precision=args.parallel.mixed_precision != "fp32"))
        from hetu_galvatron_tpu.observability.tracing import span

        def stepped(sp_, so_, b):
            with span("tp/overlap_step"):
                return step_fn(sp_, so_, b)

        return stepped

    # batch-size ramp (reference --rampup-batch-size): the micro size
    # gbsz/chunks stays FIXED; only the microbatch count varies per step
    calc = rebatch = None
    if args.train.rampup_batch_size:
        from hetu_galvatron_tpu.runtime.microbatches import (
            MicroBatchCalculator,
            Rebatcher,
        )

        chunks0 = max(hpc.chunks, 1)
        if hpc.global_bsz % chunks0:
            raise ValueError(
                f"global_bsz {hpc.global_bsz} % chunks {chunks0} != 0")
        micro = hpc.global_bsz // chunks0
        start = int(args.train.rampup_batch_size[0])
        if start < micro and not args.train.decrease_batch_size_if_needed:
            raise ValueError(
                f"rampup start batch size {start} is below the fixed micro "
                f"size global_bsz/chunks = {micro}: the ramp varies the "
                "microbatch COUNT at a constant micro shape (XLA-static), "
                "so start must be >= global_bsz/chunks — lower chunks, "
                "raise the start, or set "
                "train.decrease_batch_size_if_needed=true to clamp")
        calc = MicroBatchCalculator(
            hpc.global_bsz, micro, 1,
            args.train.rampup_batch_size,
            args.train.decrease_batch_size_if_needed)
        state.log(
            f"batch-size ramp: start {calc.start_global_batch_size} "
            f"(running {calc.current_running_global_batch_size}) -> "
            f"{hpc.global_bsz} by {calc.batch_size_increment} over "
            f"{calc.ramp_samples} samples (micro {calc.micro_batch_size})")
        rebatch = Rebatcher(base_iter)

    from hetu_galvatron_tpu.models.modules import compute_dtype_of

    compute_dtype = compute_dtype_of(args.parallel.mixed_precision)
    losses = []
    val_losses = []
    # per-path eval fn(sp, raw_batch) -> float loss; set below once the
    # execution path (spmd / pipeline) is built
    eval_box: Dict[str, Any] = {}

    def run_eval(sp, iterator) -> float:
        vs = [eval_box["fn"](sp, next(iterator))
              for _ in range(max(args.train.eval_iters, 1))]
        return float(np.mean(vs))

    exit_code = None
    consumed_box = [0]  # ramped-run sample counter (survives maybe_resume)

    def train_state_at(step, samples, batches=None):
        """Full-state-resume payload stored in the checkpoint's meta.json:
        data-stream position (committed batches at fixed batch size —
        ``data_iter.batches_consumed``, which stays exact even after a
        geometry-changed resume — or consumed samples under a ramp), the
        RNG seed the per-step dropout keys derive from, the rerun
        machine's fault history, and the telemetry step."""
        if batches is None:
            batches = step
        ts = {"step": step, "seed": args.train.seed, "telemetry_step": step,
              "batches_consumed": batches if calc is None else None,
              "consumed_samples": samples if calc is not None else None,
              # goodput totals as of this commit + a wall stamp: the
              # resuming process books the commit-to-resume gap (dead
              # attempt's discarded work + downtime) as restart_lost
              "goodput": goodput.state_dict()}
        if rerun.enabled:
            ts["rerun"] = rerun.state_dict()
        return ts

    # one save policy for both cadences (step interval + ckpt.interval_s
    # wall cadence) and both write modes (sync/orbax-async, or the
    # on-device-snapshot writer thread when ckpt.snapshot_async) —
    # chaos mid-save faults ride the same hooks seam production uses
    cadence = CheckpointCadence(
        args.ckpt, hpc=hpc, goodput=goodput, log=state.log,
        hooks=(chaos.save_hooks() if chaos is not None else None))

    def maybe_save(it, sp, so):
        if cadence.due(it):
            with span("train/save", step=it):
                cadence.save(it + 1, sp, so,
                             train_state=train_state_at(
                                 it + 1, consumed_box[0],
                                 batches=data_iter.batches_consumed))
                state.log(f"saved checkpoint at iter {it + 1}")

    def maybe_resume(sp, so):
        """Restore (sp, so, start_iter) and fast-forward the data stream so
        a resumed run consumes the batches an uninterrupted run would.

        Checkpoints written by this runtime carry a ``train_state`` payload
        (exact data position, seed, rerun history, telemetry step) making
        the resume step-for-step continuous. Checkpoints without it (older
        runs, converted imports) fall back to reconstructing the position
        from the step number; even when plan resharding is allowed
        (strict_plan off), the stored plan's global_bsz is compared so the
        fast-forward skips the SAMPLES the original run consumed, not
        `start` batches at the new size — preserving data order across a
        batch-size-changing resume (ADVICE r2; the reference asserts plan
        equality unconditionally)."""
        import math as _math

        nonlocal exit_code

        start = 0
        if args.ckpt.load:
            ckdir = latest_checkpoint(args.ckpt.load)
            if ckdir:
                if elastic is not None:
                    # topology-changed resume: the checkpoint's arrays are
                    # laid out for the OLD plan — gather to canonical and
                    # re-lay them onto the new engine's templates
                    # (runtime/reshard.py), billed to the reshard bucket
                    from hetu_galvatron_tpu.runtime.reshard import (
                        ReshardError,
                        resume_elastic,
                    )
                    from hetu_galvatron_tpu.runtime.rerun_machine import (
                        EXIT_CODE_FAILED_ON_RESULT_VALIDATION,
                    )

                    try:
                        with goodput.measure("reshard"):
                            sp, so, start = resume_elastic(
                                ckdir, sp, so,
                                tie_word_embeddings=cfg.tie_word_embeddings,
                                num_experts=cfg.num_experts or 0)
                    except ReshardError as e:
                        # same terminal contract as a rejected re-plan: a
                        # deterministic reshard failure reproduces on
                        # every restart — exit 17 with a postmortem, do
                        # NOT hand the supervisor a crash to loop on.
                        # start = train_iters runs zero iterations and
                        # the normal result path carries the code out.
                        state.log(f"elastic resume failed terminally: {e}")
                        if recorder is not None:
                            recorder.note(
                                "elastic_replan", reason=str(e),
                                live_world=world,
                                stored_world=elastic["stored_world"])
                            recorder.dump("elastic_reshard_failed")
                        else:
                            _flight_dump_elastic(
                                args, str(e), world,
                                elastic["stored_world"],
                                "elastic_reshard_failed")
                        exit_code = EXIT_CODE_FAILED_ON_RESULT_VALIDATION
                        return sp, so, args.train.train_iters
                    state.log(
                        f"elastic resume: resharded {ckdir} "
                        f"({elastic['stored_world']} -> {world} devices) "
                        f"onto plan [{hpc.describe()}] at iter {start}")
                else:
                    # resilient restore: a corrupted newest checkpoint
                    # (truncated meta.json, missing payload leaf, stray
                    # COMMITTED marker over a torn payload) falls back to
                    # the previous committed step with a warning, never a
                    # traceback — losing save_interval steps beats losing
                    # the run
                    with goodput.measure("resume_replay"):
                        res = load_latest_resilient(
                            args.ckpt.load, sp, so, hpc=hpc,
                            strict_plan=args.ckpt.distributed_checkpoint,
                            expected_world=world, log=state.log)
                    if res is None:
                        state.log(f"warning: {args.ckpt.load}: committed "
                                  "checkpoint vanished before resume; "
                                  "starting fresh")
                        return sp, so, 0
                    sp, so, start, ckdir = res
                    state.log(f"resumed from {ckdir} at iter {start}")
                # the supervisor's cross-process GC lease protected this
                # restore; now that the read landed, retention may proceed
                clear_resume_pin(args.ckpt.load)
                meta, meta_err = try_read_checkpoint_meta(ckdir)
                if meta_err is not None:
                    state.log(f"warning: {ckdir}/meta.json unreadable "
                              f"({meta_err}); resuming without the "
                              "train_state payload (position reconstructed "
                              "from the step number)")
                stored = meta.get("hybrid_parallel_config") or {}
                ts = meta.get("train_state") or {}
                if ts.get("goodput"):
                    # restore committed totals; the wall gap since the
                    # commit lands in restart_lost
                    goodput.load_state_dict(ts["goodput"])
                if recorder is not None:
                    recorder.note("resume", ckdir=ckdir, step=start)
                sbsz = stored.get("global_bsz")
                if ts.get("seed") not in (None, args.train.seed):
                    state.log(
                        f"warning: checkpoint seed {ts['seed']} != current "
                        f"{args.train.seed}: the replayed data stream and "
                        "dropout keys will differ from the original run")
                if ts.get("rerun") and rerun.enabled:
                    # fault history + spike EMA survive the restart, so a
                    # resume-to-disambiguate relaunch still knows the
                    # suspect iteration and thresholds stay warm
                    rerun.load_state_dict(ts["rerun"])
                if calc is not None:
                    # replay the ramp: skip exactly the samples the original
                    # run consumed over its first `start` iterations. This
                    # replays the CURRENT schedule — if the stored plan's
                    # batch geometry differs, the sample count cannot be
                    # reconstructed (the ramp triple is not in the plan
                    # fingerprint), so warn loudly instead of silently
                    # misaligning (mirrors the non-ramp branch below).
                    if (sbsz not in (None, hpc.global_bsz)
                            or stored.get("chunks") not in (None, hpc.chunks)):
                        state.log(
                            "warning: resuming a RAMPED run with a different "
                            f"batch geometry (stored global_bsz/chunks "
                            f"{sbsz}/{stored.get('chunks')} vs current "
                            f"{hpc.global_bsz}/{hpc.chunks}): the replayed "
                            "data schedule will not match the original run")
                    consumed = 0
                    for _ in range(start):
                        calc.update(consumed)
                        n = calc.current_running_global_batch_size
                        rebatch.next_batch(n)
                        consumed += n
                    if ts.get("consumed_samples") not in (None, consumed):
                        state.log(
                            f"warning: replayed ramp consumed {consumed} "
                            f"samples but the checkpoint recorded "
                            f"{ts['consumed_samples']}: the ramp schedule "
                            "changed since the original run")
                    consumed_box[0] = consumed
                    if telemetry is not None:
                        # ramped run: token accounting must use the SAMPLES
                        # actually consumed, not step * target batch size
                        telemetry.resume_from(
                            ts.get("telemetry_step", start),
                            samples=consumed)
                    return sp, so, start
                skip = ts.get("batches_consumed")
                if skip is None:
                    skip = start  # legacy checkpoint: position := step
                resumed_samples = None
                if sbsz and sbsz != hpc.global_bsz:
                    # token accounting must reflect what the ORIGINAL run
                    # consumed, not step * the new batch size
                    resumed_samples = skip * sbsz
                    skip = int(_math.ceil(skip * sbsz / hpc.global_bsz))
                    state.log(
                        f"warning: resuming a run trained at global_bsz "
                        f"{sbsz} with global_bsz {hpc.global_bsz}; "
                        f"fast-forwarding {skip} batches "
                        f"({resumed_samples} samples) to preserve data "
                        "order")
                elif stored.get("chunks") not in (None, hpc.chunks):
                    state.log(
                        f"warning: checkpoint chunks {stored.get('chunks')} "
                        f"!= current {hpc.chunks}; gradient accumulation "
                        "boundaries will differ from the original run")
                if telemetry is not None:
                    telemetry.resume_from(ts.get("telemetry_step", start),
                                          samples=resumed_samples)
                with goodput.measure("resume_replay"):
                    skip_batches(data_iter, skip)
        return sp, so, start

    use_dropout = (cfg.hidden_dropout > 0.0 or cfg.attention_dropout > 0.0)
    drop_key = jax.random.key(args.train.seed) if use_dropout else None
    # what the compiled step contains (filled after the first step)
    from hetu_galvatron_tpu.observability.trace_analysis import (
        GATED_NORM_SCOPE,
        GDN_SCAN_SCOPE,
        SELECTIVE_SCOPE,
        SSD_SCOPE,
        conv_kernel_calls,
        cores_recomputed,
        experts_kernel_calls,
        kda_kernel_calls,
        kda_loops,
        record_step_scopes,
        scans_recomputed,
        step_hlo,
    )

    from hetu_galvatron_tpu.models.modules import MIXERS

    step_report: Dict[str, Any] = {}
    it_box = [0]  # the iteration run_loop is in, for the spans below

    def phase(name):
        """One of the flat sibling spans that tile an iteration of
        run_loop (``train/data`` ... ``train/check``): ``span_ms{path=
        <name>}`` in the registry on every run, and inside a profiler
        window a TraceMe on the device trace's clock that carries the
        iteration as ``step``. None adds a device sync or moves a line."""
        return span(name, step=it_box[0])

    def run_loop(sp, so, step_fn):
        """Shared iteration driver for both execution paths. step_fn(sp, so,
        raw_batch) -> (sp, so, metrics)."""
        nonlocal exit_code
        drill.arm(start_iter)
        consumed_prev = consumed_box[0]
        guard.__enter__()  # trap SIGTERM/SIGINT for the loop's duration
        try:
            for it in range(start_iter, args.train.train_iters):
                profiler.time_start(it)
                it_t0 = time.perf_counter()
                it_box[0] = it
                with phase("train/data"):
                    consumed_prev = consumed_box[0]
                    if chaos is not None:
                        # fault plan fires BEFORE the update: 'crash at
                        # step k' loses exactly the steps since the last
                        # commit — the RPO the drill asserts on
                        chaos.on_step(it)
                    if calc is not None:
                        if calc.update(consumed_box[0]):
                            state.log(
                                f"ramping global batch size to "
                                f"{calc.current_running_global_batch_size} "
                                f"({calc.num_micro_batches} microbatches)")
                        batch = rebatch.next_batch(
                            calc.current_running_global_batch_size)
                        consumed_box[0] += \
                            calc.current_running_global_batch_size
                    else:
                        batch = next(data_iter)
                    if use_dropout:
                        # per-iteration rng; captured by the batch so a
                        # rerun-machine re-execution replays the SAME
                        # dropout mask (deterministic fault attribution)
                        batch = dict(batch)
                        batch["dropout_rng"] = jax.random.fold_in(
                            drop_key, it)
                    # keep pre-update state alive only when the rerun
                    # machine may re-execute the step for fault attribution
                    prev = (sp, so) if rerun.enabled else None
                # train/h2d and train/dispatch are inside spmd_step; the
                # pp>1 engines keep their own pp/* spans
                sp, so, metrics = step_fn(sp, so, batch)
                if telemetry is not None:
                    # before any sync below: the hook's own timing must see
                    # the async cadence, and it never touches device values.
                    # During a batch-size ramp the tokens-per-step must
                    # track the RUNNING batch size, not the target
                    with phase("train/telemetry"):
                        if calc is not None:
                            telemetry.global_batch_size = \
                                calc.current_running_global_batch_size
                        telemetry(it, metrics)
                with phase("train/sync"):
                    # the log line's values travel with the step: their
                    # host copies queue behind it on the device
                    profiler.start_log_copies(it, metrics)
                    profiler.time_end(it, sync=metrics.get("loss"))
                    # goodput: the synced step wall (profiler.time_end
                    # blocks on the loss). Each attempt's first iteration
                    # pays the jit compile, booked as recompile, not
                    # productive; checkpoint saves are measured separately
                    # below
                    goodput.add(
                        "recompile" if it == start_iter
                        else "productive_step",
                        time.perf_counter() - it_t0)
                with phase("train/lr"):
                    # the device is empty from here to the next dispatch,
                    # so nothing here runs on it: the log line's learning
                    # rate is a host look-up (the optimizer has its own
                    # schedule inside the step). With the profiler off
                    # nothing blocked in train/sync: train/log's read of
                    # the loss is where the host first waits
                    lr = host_lr(it) if profiler.prints(it) else None
                with phase("train/log"):
                    profiler.iteration_log(it, metrics, lr=lr)
                with phase("train/check"):
                    # at-step-k fault drill: may corrupt the loss
                    # (nan/spike, exercising the rerun machine), raise
                    # InjectedCrash, or deliver a real SIGTERM the guard
                    # converts to a boundary stop — all AFTER the update,
                    # BEFORE any save
                    lossf = drill.apply(float(metrics["loss"]), it)
                    rerun.validate_result(
                        lossf, it,
                        rerun_fn=(
                            (lambda: float(
                                step_fn(*prev, batch)[2]["loss"]))
                            if prev is not None else None),
                        data_iterator=data_iter if calc is None else None)
                    if calc is None:
                        data_iter.advance()
                    losses.append(lossf)
                if (valid_iter is not None and "fn" in eval_box
                        and args.train.eval_interval
                        and (it + 1) % args.train.eval_interval == 0):
                    with phase("train/eval"):
                        v = run_eval(sp, valid_iter)
                        val_losses.append({"iter": it + 1, "loss": v})
                        state.log(
                            f"iter {it + 1}: validation loss {v:.4f} "
                            f"({args.train.eval_iters} held-out batches)")
                # check for a fault BEFORE the interval save: the faulty update
                # must never be persisted (a step_{it+1} checkpoint would shadow
                # the pre-fault step_{it} one on resume)
                exit_code = rerun.exit_code_requested()
                if exit_code is None:
                    maybe_save(it, sp, so)
                if exit_code is not None:
                    state.log(f"rerun machine requested exit (code {exit_code});"
                              " checkpointing pre-fault state")
                    if recorder is not None:
                        # NaN/validation halt: leave the postmortem (ring
                        # + metric snapshot) next to the metrics stream
                        recorder.dump(f"rerun_exit_{exit_code}")
                    if args.ckpt.save and prev is not None:
                        # save the PRE-update state at iter `it`: the faulty
                        # update must not be persisted, and the relaunch re-runs
                        # the suspect iteration to disambiguate
                        with goodput.measure("checkpoint_save"):
                            # never race an in-flight save; the drain is
                            # save time too (async saves bill their wall
                            # here, not at dispatch)
                            cadence.drain()
                            save_checkpoint(
                                args.ckpt.save, it, prev[0], prev[1],
                                hpc=hpc,
                                # position excludes the suspect iteration's
                                # batch: the relaunch must re-consume it
                                train_state=train_state_at(
                                    it, consumed_prev,
                                    batches=data_iter.batches_consumed - 1),
                                keep_last=args.ckpt.keep_last,
                                hooks=cadence.hooks)
                    break
                if guard.requested():
                    # preemption/interrupt at a step boundary: the update
                    # for iter `it` is complete, so checkpoint the
                    # POST-update state at step it+1 and exit — SIGTERM
                    # maps to restartable 18, an operator's SIGINT to
                    # non-restartable 130 (auto_restart must not resurrect
                    # a deliberately stopped run)
                    exit_code = guard.exit_code()
                    state.log("stop signal received; checkpointing "
                              f"at iter {it + 1} and exiting "
                              f"(code {exit_code})")
                    ck = args.ckpt
                    if ck.save and not (ck.save_interval and
                                        (it + 1) % ck.save_interval == 0):
                        # the interval save above did not already cover
                        # this exact step
                        with goodput.measure("checkpoint_save"):
                            cadence.drain()
                            save_checkpoint(
                                ck.save, it + 1, sp, so, hpc=hpc,
                                train_state=train_state_at(
                                    it + 1, consumed_box[0],
                                    batches=data_iter.batches_consumed),
                                keep_last=ck.keep_last,
                                hooks=cadence.hooks)
                    break
        except BaseException as e:
            # crash forensics BEFORE re-raising: the dump (ring + metric
            # snapshot + this traceback) is atomic and dump() never
            # raises, so the original fault surfaces untouched
            if recorder is not None:
                recorder.dump("crash", exc=e)
            raise
        finally:
            guard.__exit__()
            if chaos is not None:
                chaos.uninstall()
            try:
                # drain async saves even on the crash path: a supervised
                # in-process restart must never inherit live background
                # writes or stale pending commits from a dead attempt.
                # The blocking drain IS checkpoint time — async saves
                # bill their real wall here, not at dispatch
                with goodput.measure("checkpoint_save"):
                    cadence.drain()
            except Exception as e:  # noqa: BLE001 — never mask the crash
                state.log(f"warning: async checkpoint drain failed: {e}")
            # crash-safe: flush an open XLA trace window + the metrics
            # stream so both survive the exception they may help debug
            profiler.stop_trace()
            if (telemetry is not None and args.profile.trace_dir
                    and args.observability.audit):
                # close the loop: attribute the captured device trace and
                # diff it against the plan's cost-model predictions
                # (audit/* gauges + plan_audit event; flushed by the
                # telemetry close below). The whole block is guarded like
                # the checkpoint drain above: it runs on the crash path
                # too, and a post-mortem helper failing (e.g. an import
                # missing in a lean deployment) must neither mask the real
                # traceback nor skip the telemetry close.
                try:
                    from hetu_galvatron_tpu.observability.trace_analysis \
                        import analyze_and_audit

                    ab = ab_algos = None
                    if args.observability.audit_hardware_config:
                        from hetu_galvatron_tpu.core.search_engine.profiles \
                            import read_alpha_beta, read_alpha_beta_algos

                        try:
                            ab = read_alpha_beta(
                                args.observability.audit_hardware_config)
                            ab_algos = read_alpha_beta_algos(
                                args.observability.audit_hardware_config)
                        except Exception as e:  # noqa: BLE001
                            state.log(f"warning: audit_hardware_config "
                                      f"unreadable ({e}); volume-only audit")
                    # searched plans embed the cost model's per-layer
                    # compute prediction (ms); audit_plan takes SECONDS
                    pred_s = None
                    if hpc.predicted_layer_compute_ms:
                        pred_s = [v / 1e3
                                  for v in hpc.predicted_layer_compute_ms]
                    table = analyze_and_audit(
                        args.profile.trace_dir, hpc, cfg,
                        registry=telemetry.registry, alpha_beta=ab,
                        alpha_beta_algos=ab_algos,
                        mixed_precision=(
                            args.parallel.mixed_precision != "fp32"),
                        predicted_layer_s=pred_s)
                    if table:
                        state.log(
                            f"plan audit: {len(table['rows'])} components "
                            f"over {table['steps']} traced step(s) — see "
                            "the plan_audit event / audit/* gauges in the "
                            "metrics stream (cli/summarize.py renders the "
                            "table)")
                    if table and args.observability.calibration_dir:
                        # close the OTHER half of the loop: feed the
                        # audit's residuals into the persistent store,
                        # re-fit the α-β curves over everything
                        # accumulated on this hardware, and run the
                        # plan-regret sentinel over the plan's embedded
                        # runner-ups (calibration/* gauges + at most one
                        # plan_regret event; never raises)
                        from hetu_galvatron_tpu.observability.calibration \
                            import run_calibration

                        cal = run_calibration(
                            table, hpc, cfg,
                            calibration_dir=(
                                args.observability.calibration_dir),
                            registry=telemetry.registry,
                            prior_config=(
                                args.observability.audit_hardware_config),
                            world=world,
                            min_points=(
                                args.observability.calibration_min_points),
                            window_days=(
                                args.observability
                                .calibration_window_days),
                            max_points_per_curve=(
                                args.observability
                                .calibration_max_points),
                            regret_threshold=(
                                args.observability.regret_threshold),
                            plan_path=(
                                args.parallel.galvatron_config_path
                                if args.parallel.config_mode == "json"
                                else None),
                            mixed_precision=(
                                args.parallel.mixed_precision != "fp32"),
                            recorder=recorder)
                        if cal.get("error"):
                            state.log("warning: calibration failed: "
                                      f"{cal['error']}")
                        else:
                            msg = (f"calibration: +{cal['points_appended']}"
                                   f" residual point(s) "
                                   f"({cal['points_total']} total), "
                                   f"{cal['curves_fitted']} curve(s) "
                                   "re-fit")
                            if cal.get("profile_path"):
                                msg += f" -> {cal['profile_path']}"
                            reg = cal.get("regret")
                            if reg and reg.get("triggered"):
                                msg += (" — PLAN REGRET: a runner-up now "
                                        "beats the incumbent by "
                                        f"{reg['regret_ms']:.3f} ms/step "
                                        "under calibrated curves "
                                        "(plan_regret event emitted)")
                            state.log(msg)
                except Exception as e:  # noqa: BLE001 — never mask the crash
                    state.log(f"warning: plan audit failed: {e}")
            if telemetry is not None:
                # export the goodput partition before the final flush so
                # the last records in the stream carry it
                goodput.flush(telemetry.registry)
                telemetry.close()
        return sp, so

    if hpc.pp_deg > 1:
        # schedule impl selection (pipeline.schedule_impl): "compiled" fuses
        # the whole 1F1B step into one SPMD program with ppermute stage
        # transfers; plans it cannot express fall back to the host-sequenced
        # engine with a logged reason (the general path)
        eng = None
        if args.pipeline.schedule_impl == "compiled":
            from hetu_galvatron_tpu.runtime.compiled_pipeline import (
                CompiledPipelineEngine,
            )

            reason = CompiledPipelineEngine.unsupported_reason(
                cfg, hpc, data=args.data)
            if reason is not None:
                state.log("pipeline.schedule_impl=compiled cannot express "
                          f"this plan ({reason}); falling back to the host "
                          "engine")
            else:
                # donation halves live model-state memory but is only safe
                # when the rerun machine never re-runs pre-update buffers.
                # tp_overlap rides INSIDE the fused program since the stage
                # axis was de-vmapped (stage-stacked shard_map kernels)
                eng = CompiledPipelineEngine(
                    cfg, hpc, args.train, devices=state.devices,
                    compute_dtype=compute_dtype,
                    dcn_slices=args.parallel.dcn_slices,
                    donate=not rerun.enabled,
                    tp_overlap=tp_overlap_on)
                if tp_overlap_on and not eng.tp_overlap:
                    state.log("tp_overlap: no eligible layer under the "
                              f"compiled schedule ({eng.overlap_reason}); "
                              "running GSPMD collectives")
                    tp_overlap_on = False
                    overlapped_layers = []
                state.log("pipeline schedule: compiled single-program 1F1B "
                          f"(bubble_frac {eng.bubble_frac():.3f}"
                          + (", overlapped-TP rings inside"
                             if eng.tp_overlap else "") + ")")
        if eng is None:
            eng = PipelineEngine(cfg, hpc, args.train, devices=state.devices,
                                 compute_dtype=compute_dtype,
                                 dcn_slices=args.parallel.dcn_slices,
                                 tp_overlap=tp_overlap_on)
        # the engines slice a whole tree per stage: it lives on the default
        # device only until every stage holds its shards
        with span("setup/init"):
            sp = eng.split_params(init_params(init_key), axes)
            so = eng.init_opt(sp, axes)
        with span("setup/resume"):
            sp, so, start_iter = maybe_resume(sp, so)
        if valid_iter is not None or test_iter is not None:
            eval_box["fn"] = lambda sp_, raw: eng.eval_step(sp_, raw)["loss"]
        if calc is None:
            sp, so = run_loop(sp, so, finish_tp_overlap_setup(eng.train_step))
        else:
            # the stage jits are microbatch-shaped: a ramp reuses them all
            sp, so = run_loop(sp, so, finish_tp_overlap_setup(
                lambda sp_, so_, b: eng.train_step(
                    sp_, so_, b, num_microbatches=calc.num_micro_batches)))
        step_report["mosaic_custom_calls"] = getattr(
            eng, "mosaic_custom_calls", None)
    else:
        with span("setup/init"):
            mesh = build_mesh(world, 1, devices=state.devices,
                              dcn_slices=args.parallel.dcn_slices)
            if ep_report:
                # which chip of the ep group each device is: a trace names
                # its planes by device id, the log line its chips by index
                from hetu_galvatron_tpu.runtime.mesh import (
                    devices_along,
                    lower_strategy,
                )

                ep_report["chip_devices"] = devices_along(
                    mesh, lower_strategy(exchanged[0], mesh).ep_axes)
                for chip, ids in enumerate(ep_report["chip_devices"]):
                    for device in ids:
                        get_registry().gauge(
                            "ep/chip_of_device",
                            device=str(device)).set(chip)
            # donation halves live model-state memory but is only safe when
            # the rerun machine will never re-call the step on pre-update
            # buffers
            step, pspecs, ospecs, batch_shd = make_spmd_train_step(
                cfg, hpc, mesh, axes, tx, params,
                compute_dtype=compute_dtype,
                donate=not rerun.enabled, tp_overlap=tp_overlap_on)
            place_box["batch_shd"] = batch_shd
            nshd = lambda specs: jax.tree.map(
                lambda s: NamedSharding(mesh, s), specs,
                is_leaf=lambda x: isinstance(x, PartitionSpec))
            sp = jax.jit(init_params, out_shardings=nshd(pspecs))(init_key)
            so = jax.jit(tx.init, out_shardings=nshd(ospecs))(sp)
        with span("setup/resume"):
            sp, so, start_iter = maybe_resume(sp, so)
        # ramp: one jitted step per distinct microbatch COUNT (micro shape
        # fixed), compiled lazily as the ramp reaches each count
        step_cache = {max(hpc.chunks, 1): step}

        def get_step(ch):
            if ch not in step_cache:
                step_cache[ch] = make_spmd_train_step(
                    cfg, hpc, mesh, axes, tx, params,
                    compute_dtype=compute_dtype,
                    donate=not rerun.enabled, chunks=ch,
                    tp_overlap=tp_overlap_on)[0]
            return step_cache[ch]

        def spmd_step(sp, so, raw):
            raw = dict(raw)
            # the rng key is per-step scalar data: placed replicated, not
            # under the [B, ...] batch sharding
            rng = raw.pop("dropout_rng", None)
            with phase("train/h2d"):
                b = jax.device_put(raw, batch_shd)
            if rng is not None:
                b["dropout_rng"] = rng
            fn = step if calc is None else get_step(calc.num_micro_batches)
            # on the first iteration: tracing, lowering, compile or cache load
            with phase("train/dispatch"):
                out = fn(sp, so, b)
            if "mosaic_custom_calls" not in step_report:
                # what the compiled step contains and needs, from the one
                # executable the call above made (.lower() and .compile()
                # return what that call cached: 0.05 to 0.4 s on the chip):
                # Mosaic calls and collectives in its HLO, every
                # instruction's scope, phase and collective class (one walk
                # over the text, kept for a reader of a trace), and XLA's
                # static memory as step/static_bytes{part=...} gauges
                with span("setup/step_report"):
                    compiled = fn.lower(out[0], out[1], b).compile()
                    # (as_text: 0.2 s on four chips)
                    hlo_text = compiled.as_text()
                    found = step_hlo(hlo_text)
                    record_step_scopes(found)
                    step_report.update(
                        mosaic_custom_calls=found["mosaic_custom_calls"],
                        collectives=found["collectives"],
                        relayouts=found["relayouts"],
                        scope_instructions=found["scopes"])
                    get_registry().gauge("step/relayout_bytes").set(
                        found["relayouts"]["bytes"])
                    # the step's data, followed: the prefetches XLA made
                    # (copy-start / slice-start pairs), their bytes, and the
                    # instructions under no scope that no scoped one uses
                    # or feeds
                    step_report["flow"] = found["flow"]
                    for what, n in found["flow"].items():
                        get_registry().gauge(f"step/{what}").set(n)
                    # attention cores and recurrent scans run again under
                    # per-layer remat: the flash / scan forward calls the map
                    # puts in the recompute phase
                    for part, count in (("cores", cores_recomputed),
                                        ("scans", scans_recomputed)):
                        step_report[f"{part}_recomputed"] = count(found)
                        get_registry().gauge(f"step/{part}_recomputed").set(
                            step_report[f"{part}_recomputed"])
                    if expert_bodies:
                        get_registry().gauge("moe/whole_body_layers").set(
                            sum(body == "whole"
                                for body in expert_bodies.values()))
                    step_report["step_map"] = {
                        "instructions": len(found["map"]["instructions"]),
                        "inferred": len(found["map"]["inferred"]),
                        "unnamed": len(found["map"]["tails"])}
                    if any(m == "kda" for m, _ in kinds):
                        # how many blocks run the delta rule and at which
                        # chunk length: by the kernels' calls where the
                        # recurrence runs in them (mosaic_calls, the Mosaic
                        # calls under its scope; 0 = the jax.numpy form),
                        # else by the compiled step's own loops (exact
                        # where the chunk divides the sequence)
                        step_report["kda"] = kda_kernel_calls(hlo_text)
                        if not step_report["kda"]["mosaic_calls"]:
                            loops = kda_loops(hlo_text)
                            step_report["kda"].update(
                                blocks=loops["blocks"],
                                chunk=-(-cfg.seq_length
                                        // max(loops["chunks"], 1)))
                        for part, v in step_report["kda"].items():
                            get_registry().gauge(f"kda/{part}").set(v)
                    for kind, scope, name in (
                            ("mamba", SSD_SCOPE, "ssd"),
                            ("mamba", GATED_NORM_SCOPE, "gated_norm"),
                            ("mamba1", SELECTIVE_SCOPE, "selective"),
                            ("linear_attention", GDN_SCAN_SCOPE, "gdn")):
                        if any(m == kind for m, _ in kinds):
                            # whether the kernels of the scan (of a mamba
                            # block's gated norm) engaged: the Mosaic
                            # calls under its scope, 0 = the jax.numpy form
                            step_report[f"{name}_mosaic_calls"] = sum(
                                n in found["mosaic_calls"]
                                for n in found["scopes"].get(scope, ()))
                            get_registry().gauge(f"{name}/mosaic_calls").set(
                                step_report[f"{name}_mosaic_calls"])
                    if any(ff == "experts" for _, ff in kinds):
                        # whether the grouped matmuls' kernels engaged:
                        # their Mosaic calls under moe/experts, 0 =
                        # lax.ragged_dot
                        step_report["experts_mosaic_calls"] = (
                            experts_kernel_calls(found))
                        get_registry().gauge("experts/mosaic_calls").set(
                            step_report["experts_mosaic_calls"])
                    if any(m and MIXERS[m].reads("conv") for m, _ in kinds):
                        # whether the convolution's kernels engaged: their
                        # calls by phase, one a block in each where they
                        # did, zeros = the jax.numpy form
                        step_report["conv_kernel_calls"] = (
                            conv_kernel_calls(found))
                        for part, v in step_report[
                                "conv_kernel_calls"].items():
                            get_registry().gauge("conv/kernel_calls",
                                                 phase=part).set(v)
                    if any(c.startswith("flash[w") for c in attention_cores):
                        # the score tiles the flash kernels' loops visit in
                        # the window blocks over those of the causal
                        # triangle of the same calls, by the window and
                        # tiles the calls were built with
                        # (``flash_attention.WINDOWED_CALLS``); a window
                        # block whose call carried no window ran the
                        # triangle, masked or not: 100
                        visited = triangle = 0
                        for S, heads, bq, bk, w in WINDOWED_CALLS:
                            v, t = band_tiles(S, bq, bk, w)
                            visited += heads * v
                            triangle += heads * t
                        step_report["band_tiles_pct"] = (
                            100.0 * visited / triangle if triangle else 100.0)
                        get_registry().gauge("flash/band_tiles_pct").set(
                            step_report["band_tiles_pct"])
                    if tower_report:
                        tower_report["pairs_tiled"] = (
                            hpc.global_bsz * tower_pairs_tiled(cfg, use_flash))
                        get_registry().gauge("tower/pairs_tiled").set(
                            tower_report["pairs_tiled"])
                    # the distinct flash calls the step was built with, by
                    # what the kernels index: the projections' own rows, or
                    # head-major copies between transposes
                    # (``flash_attention.row_layout`` of the call's widths)
                    for path, gauge in (("rows", "row_layout_calls"),
                                        ("transposed", "transposed_calls")):
                        step_report[gauge] = sum(
                            c[0] == path for c in LAYOUT_CALLS)
                        get_registry().gauge(f"flash/{gauge}").set(
                            step_report[gauge])
                    step_report["static_memory"] = compiled_memory_bytes(
                        compiled)
                    # which of the blocks whose plan bit is set hold their
                    # values and which make them again, and the bytes the
                    # step program counted for the kept against the budget
                    # the device left (parallel/spmd.py::KeptStep)
                    from hetu_galvatron_tpu.parallel.spmd import kept_report

                    kept_blocks = kept_report(fn, cfg, hpc)
                    step_report["kept_blocks"] = kept_blocks
                    for name in ("blocks_kept", "blocks_recomputed"):
                        for stack, n in kept_blocks[name].items():
                            get_registry().gauge(f"step/{name}",
                                                 stack=stack).set(n)
                    for name, key in (("kept_bytes", "kept_bytes"),
                                      ("kept_budget_bytes", "budget_bytes"),
                                      ("kept_fallback", "fallback")):
                        get_registry().gauge(f"step/{name}").set(
                            kept_blocks[key])
                    for part, v in step_report["static_memory"].items():
                        get_registry().gauge("step/static_bytes",
                                             part=part).set(v)
                    for op, n in step_report["collectives"].items():
                        get_registry().gauge("step/collectives",
                                             op=op).set(n)
                state.log("step report: " + ", ".join(
                    f"{n} {op}" for op, n
                    in step_report["collectives"].items())
                    + ", blocks " + " ".join(
                        f"{n} x {kind}" for kind, n in blocks.items())
                    + "".join(
                        f", {len(names)} instructions under {scope}"
                        for scope, names in step_report[
                            "scope_instructions"].items() if names)
                    + ", {instructions} instructions mapped ({unnamed} "
                      "under no scope, {inferred} by what they fuse)".format(
                          **step_report["step_map"])
                    + ", step/relayout_bytes {bytes} in {count}".format(
                        **step_report["relayouts"])
                    + (" (the largest {opcode} {shape} <- {op_name})".format(
                        **step_report["relayouts"]["largest"])
                       if step_report["relayouts"]["largest"] else "")
                    + ", step/prefetches {prefetches} of "
                      "step/prefetch_bytes {prefetch_bytes}, "
                      "step/unowned_instructions "
                      "{unowned_instructions}".format(**step_report["flow"])
                    + f", {step_report['mosaic_custom_calls']} Mosaic calls"
                    + (f" ({step_report['ssd_mosaic_calls']} under "
                       f"{SSD_SCOPE}), ssd/groups {cfg.mamba_n_groups}, "
                       "gated_norm/mosaic_calls "
                       f"{step_report['gated_norm_mosaic_calls']}"
                       if "ssd_mosaic_calls" in step_report else "")
                    + (", selective/mosaic_calls "
                       f"{step_report['selective_mosaic_calls']}"
                       if "selective_mosaic_calls" in step_report else "")
                    + (f", gdn/mosaic_calls {step_report['gdn_mosaic_calls']}"
                       if "gdn_mosaic_calls" in step_report else "")
                    + (", experts/mosaic_calls "
                       f"{step_report['experts_mosaic_calls']}"
                       if "experts_mosaic_calls" in step_report else "")
                    + (", kda/blocks {blocks} kda/chunk {chunk} "
                       "kda/mosaic_calls {mosaic_calls}".format(
                        **step_report["kda"]) if "kda" in step_report
                       else "")
                    + (", conv kernels {forward} forward {recompute} "
                       "recompute {backward} backward".format(
                        **step_report["conv_kernel_calls"])
                       if "conv_kernel_calls" in step_report else "")
                    + (", flash/band_tiles_pct "
                       f"{step_report['band_tiles_pct']:.1f}"
                       if "band_tiles_pct" in step_report else "")
                    + (f", tower/pairs_tiled {tower_report['pairs_tiled']}"
                       if tower_report else "")
                    + (", flash/row_layout_calls "
                       f"{step_report['row_layout_calls']}"
                       ", flash/transposed_calls "
                       f"{step_report['transposed_calls']}"
                       if "row_layout_calls" in step_report else "")
                    + (", ep {axes} first-chunk rows {first_chunk_rows} "
                       "pass rows {pass_rows}".format(**ep_report)
                       if ep_report else "")
                    + "".join(f", moe[{name}] {body}"
                              for name, body in expert_bodies.items())
                    + ", kept {} of {} blocks, {:.2f} of {:.2f} GiB".format(
                        sum(kept_blocks["blocks_kept"].values()),
                        sum(kept_blocks["blocks_kept"].values())
                        + sum(kept_blocks["blocks_recomputed"].values()),
                        kept_blocks["kept_bytes"] / 2**30,
                        kept_blocks["budget_bytes"] / 2**30)
                    # (what the choice cost the set-up: the count's traces,
                    # and the chosen step's compile before its first call,
                    # which that call then finds cached)
                    + " (counted in {count_s:.2f} s, checked in {check_s:.2f}"
                      " s)".format(**kept_blocks)
                    + f", {step_report['cores_recomputed']} cores recomputed,"
                    f" {step_report['scans_recomputed']} scans recomputed,"
                    f" static live peak "
                    f"{step_report['static_memory']['live_peak'] / 2**30:.3f}"
                    " GiB")
            return out

        if valid_iter is not None or test_iter is not None:
            from hetu_galvatron_tpu.parallel.spmd import make_spmd_eval_step

            eval_fn, eval_shd = make_spmd_eval_step(
                cfg, hpc, mesh, axes, compute_dtype=compute_dtype,
                tp_overlap=tp_overlap_on)

            def spmd_eval(sp_, raw):
                raw = dict(raw)
                raw.pop("dropout_rng", None)
                b = jax.device_put(raw, eval_shd)
                return float(eval_fn(sp_, b))

            eval_box["fn"] = spmd_eval

        sp, so = run_loop(sp, so, finish_tp_overlap_setup(spmd_step))

    with goodput.measure("checkpoint_save"):
        cadence.drain()
    test_loss = None
    if (test_iter is not None and "fn" in eval_box and exit_code is None
            and losses):
        # end-of-training held-out evaluation on the test split (the
        # reference runs evaluate() on the test iterator after training)
        test_loss = run_eval(sp, test_iter)
        state.log(f"test loss {test_loss:.4f} "
                  f"({args.train.eval_iters} held-out batches)")
    if args.profile.profile:
        state.log(f"mean iter time: {profiler.filtered_time_ms():.2f} ms")
    if rerun.enabled and rerun.records:
        state.log(f"rerun report: {rerun.report()}")
    return {"losses": losses, "val_losses": val_losses,
            "test_loss": test_loss, "iter_ms": profiler.filtered_time_ms(),
            "rerun": rerun.report() if rerun.enabled else None,
            "goodput": {"totals": dict(goodput.totals),
                        "frac": goodput.goodput(),
                        "restarts_survived": goodput.restarts_survived},
            "flight_dumps": list(recorder.dumped) if recorder else [],
            "attention_cores": attention_cores,
            # a model with a tower and a traffic with images: the tower's
            # blocks (their cores are the first of attention_cores), and a
            # step's patches, image positions, marked positions and the
            # (query, key) pairs the tower's mask leaves and its cores'
            # tiles cover (the tower/* gauges); None without
            "tower": tower_report or None,
            # the expert exchange: the ep degree of the blocks inside it,
            # how many there are, bytes a chip sends around them a step
            # (gauges ep/axes, ep/exchange_bytes_per_step), and the rows of
            # a chip's first chunk and of one counted pass behind it, a
            # microbatch (ep/first_chunk_rows, ep/pass_rows); None without
            "ep": ep_report or None,
            # the body each sorted expert layer's dispatcher compiled to:
            # "whole" (the first chunk is every route, no loop; their count
            # is the gauge moe/whole_body_layers) or "counted <first
            # chunk's rows>/<routes> +<a pass's rows>"
            "expert_bodies": expert_bodies,
            # blocks by "<mixer>/<feed-forward>" kind (step/blocks gauges)
            "blocks": blocks,
            # Mosaic kernels in the compiled step's HLO (pp=1), or summed
            # over the host engine's stage backward programs; None for
            # the compiled engine, which does not count them
            "mosaic_custom_calls": step_report.get("mosaic_custom_calls"),
            # flash forward kernels that step runs a second time under
            # per-layer remat (the gauge step/cores_recomputed; 0 = every
            # core's output is kept); None for the pp engines
            "cores_recomputed": step_report.get("cores_recomputed"),
            # the same of the recurrent mixers' scan kernels (the gauge
            # step/scans_recomputed; 0 = every scan's output and entering
            # states are kept); None for the pp engines
            "scans_recomputed": step_report.get("scans_recomputed"),
            # of the blocks whose plan bit is set, how many hold their
            # values and how many make them again, by stack
            # (step/blocks_kept, step/blocks_recomputed), the bytes counted
            # for the kept and the budget (step/kept_bytes,
            # step/kept_budget_bytes), and whether the step fell back to
            # the plan's flags (step/kept_fallback); None for the pp engines
            "kept_blocks": step_report.get("kept_blocks"),
            # XLA's static memory of the compiled pp=1 step, per device, in
            # bytes (the step/static_bytes gauges); None for the pp engines
            "static_memory": step_report.get("static_memory"),
            # collective instructions in that step's HLO, by opcode (the
            # step/collectives gauges); None for the pp engines
            "collectives": step_report.get("collectives"),
            # the reshape, copy and transpose instructions that step's HLO
            # holds outside its fusions, passes over an array that compute
            # nothing: their result bytes summed (the gauge
            # step/relayout_bytes), their count and the largest one's
            # opcode, shape and op_name tail; None for the pp engines
            "relayouts": step_report.get("relayouts"),
            # the asynchronous copies and slices that step's HLO holds
            # (XLA's prefetches), their destinations' bytes, and the
            # instructions under no scope that the map found no owner for
            # (the gauges step/prefetches, step/prefetch_bytes and
            # step/unowned_instructions); None for the pp engines
            "flow": step_report.get("flow"),
            # instruction names of that step's HLO under each named scope a
            # state-space block has (what the granite_* readers join a
            # trace's events to; empty lists for a model without one), and
            # how many instructions the whole map holds (the map itself:
            # trace_analysis.step_scopes()["map"], and step_map.json beside
            # a trace); None for the pp engines
            "scope_instructions": step_report.get("scope_instructions"),
            "step_map": step_report.get("step_map"),
            # the Mosaic calls among those under mixer/mamba/ssd (the gauge
            # ssd/mosaic_calls): 0 where the scan ran in its jax.numpy form
            "ssd_mosaic_calls": step_report.get("ssd_mosaic_calls"),
            # the same under mixer/mamba/gated_norm (the gauge
            # gated_norm/mosaic_calls): the skip and the gated norm
            "gated_norm_mosaic_calls": step_report.get(
                "gated_norm_mosaic_calls"),
            # the same under mixer/mamba1/scan (the gauge
            # selective/mosaic_calls); None for a model without such a block
            "selective_mosaic_calls": step_report.get(
                "selective_mosaic_calls"),
            # the same under mixer/gdn/scan (the gauge gdn/mosaic_calls);
            # None for a model without a linear_attention block
            "gdn_mosaic_calls": step_report.get("gdn_mosaic_calls"),
            # the program's own grouped-matmul kernels under moe/experts
            # (the gauge experts/mosaic_calls): 0 where lax.ragged_dot ran;
            # None for a model without an expert block
            "experts_mosaic_calls": step_report.get("experts_mosaic_calls"),
            # the blocks that run Kimi Delta Attention, their chunk length
            # and the Mosaic calls under mixer/kda/scan, by the compiled
            # step's kernel calls or, where that is 0, its loops (the
            # gauges kda/blocks, kda/chunk and kda/mosaic_calls); None for
            # a model without such a block
            "kda": step_report.get("kda"),
            # a stack whose blocks read earlier blocks' values: its blocks
            # by kind, the selective scan's chunk and the bytes kept between
            # blocks (the gauges of the same names); {} for any other
            "shared_values": shared_report,
            # the convolution's kernel calls of that step by phase (the
            # gauges conv/kernel_calls{phase=...}; zeros = the jax.numpy
            # form); None for a model no block of which convolves
            "conv_kernel_calls": step_report.get("conv_kernel_calls"),
            # the score tiles the flash kernels visit in the window blocks
            # over those of the causal triangle, percent, by the windows and
            # tiles the step's flash calls were built with (the gauge
            # flash/band_tiles_pct); None for a model without such a block
            "band_tiles_pct": step_report.get("band_tiles_pct"),
            # the distinct flash calls of that step whose kernels index the
            # projections' rows, and those that run between transposes (the
            # gauges flash/row_layout_calls and flash/transposed_calls)
            "flash_layout_calls": {
                k: step_report.get(f"{k}_calls")
                for k in ("row_layout", "transposed")},
            "exit_code": exit_code}


def _finish(out: Dict[str, Any]) -> int:
    if out.get("exit_code") is not None:
        return out["exit_code"]  # the reference's 16/17 fault contract
    if not out["losses"]:
        # e.g. resuming a run that had already reached train_iters
        print("training done: 0 iters (nothing left to train)")
        return 0
    final = out["losses"][-1]
    print(f"training done: {len(out['losses'])} iters, final loss {final:.4f}")
    return 0 if np.isfinite(final) else 1


def main(argv=None, *, result: Optional[Dict[str, Any]] = None) -> int:
    """Launcher entry. ``result``, when given, receives :func:`train`'s
    output dict (losses, attention cores, ...) of the last in-process
    attempt — ``chip_smoke.py`` and tests read it; the exit code is the
    contract everyone else uses."""
    from hetu_galvatron_tpu.cli.compile_cache import configure_compile_cache
    from hetu_galvatron_tpu.core.arguments import args_from_cli

    base_argv = list(argv if argv is not None else sys.argv[1:])
    args = args_from_cli(base_argv, mode="train_dist")
    sup = args.supervisor
    if sup.auto_restart and sup.mode == "process":
        # production restart loop: delegate to the cross-process
        # supervisor (cli/supervise.py), which relaunches this module as
        # a child per attempt — exit codes, restart budget, RESUME_PIN,
        # and world changes are then real across the process boundary.
        # Nothing jax-flavored has run yet in this process, so the
        # supervisor stays off the accelerator its children need.
        from hetu_galvatron_tpu.cli.supervise import run_supervised

        return run_supervised(args, base_argv)
    configure_compile_cache()
    if not sup.auto_restart:
        out = train(args)
        if result is not None:
            result.update(out)
        return _finish(out)

    # supervised mode: checkpoint-and-exit codes (16 resume-to-
    # disambiguate, 18 preempted) and crashes auto-restart with jittered
    # backoff, resuming from the last committed checkpoint; a persistent
    # validation fault (17) surfaces immediately
    from hetu_galvatron_tpu.runtime.supervisor import run_with_restarts

    last: Dict[str, Any] = {}

    from hetu_galvatron_tpu.runtime.checkpoint import latest_checkpoint

    def attempt() -> int:
        if args.ckpt.save and (not args.ckpt.load
                               or latest_checkpoint(args.ckpt.save)):
            # resume from this run's own progress as soon as it has a
            # committed checkpoint — a warm-start ckpt.load pointing
            # elsewhere must not make every restart retrain from the
            # warm-start step; until the first save lands, the original
            # load path (or a fresh start) still applies
            args.ckpt.load = args.ckpt.save
        out = train(args)
        last["out"] = out
        return out.get("exit_code") or 0

    # Within ONE process the device list is fixed at backend init, so
    # this probe observes a fleet change only when the supervisor wraps
    # relaunches across processes (drills inject it directly; a real
    # preemption kills the process, whose relaunch re-reads the fleet).
    from hetu_galvatron_tpu.runtime.initialize import visible_world_size

    rc = run_with_restarts(
        attempt, max_restarts=sup.max_restarts,
        base_delay=sup.backoff_base_s, max_delay=sup.backoff_max_s,
        restart_on_error=sup.restart_on_error,
        # the budget bounds crash LOOPS: whenever an attempt committed a
        # new checkpoint, the restart counter resets, so a long run on a
        # preemptible fleet survives unbounded preemptions
        progress_fn=((lambda: latest_checkpoint(args.ckpt.save))
                     if args.ckpt.save else None),
        # ... and a TOPOLOGY change is progress too: a restart that sees a
        # different world re-searches and reshards (the elastic pre-pass
        # in train()), so it must get a fresh budget, not inherit the old
        # world's crash count
        world_fn=lambda: visible_world_size(args))
    if result is not None:
        result.update(last.get("out", {}))
    if rc != 0:
        return rc
    return _finish(last["out"])


if __name__ == "__main__":
    sys.exit(main())
