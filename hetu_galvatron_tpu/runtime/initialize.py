"""Framework initialization & global run state.

Capability parity with the reference init layer (runtime/initialize.py:114-246
``initialize_galvatron`` / ``validate_args`` and runtime/parallel_state.py
globals): argument validation, seeding, device/mesh discovery, and the run's
observability writers.

TPU-native: there is no process-group bootstrap — the single-controller JAX
runtime already sees every chip (`jax.devices()`); "initialization" is
validating the plan against the visible world, seeding, and wiring loggers.
The reference's env-based RANK/WORLD_SIZE handshake and NCCL init
(initialize.py:114-160) have no equivalent because XLA owns the transport.
"""

from __future__ import annotations

import logging
import os
import random
import sys
from dataclasses import dataclass, field
from typing import Any, List, Optional

import numpy as np

from hetu_galvatron_tpu.core.args_schema import CoreArgs


@dataclass
class RunState:
    """Global run context (the reference's parallel_state globals:
    args/tokenizer/writers/memory buffer, parallel_state.py:135-305)."""

    args: CoreArgs
    devices: List[Any] = field(default_factory=list)
    world_size: int = 1
    logger: Optional[logging.Logger] = None
    tensorboard: Any = None
    wandb: Any = None

    def log(self, msg: str) -> None:
        (self.logger.info if self.logger else print)(msg)


_STATE: Optional[RunState] = None


def get_run_state() -> RunState:
    if _STATE is None:
        raise RuntimeError("initialize() has not been called")
    return _STATE


def validate_args(args: CoreArgs, world_size: int) -> None:
    """Cross-field checks (reference validate_args, initialize.py:190)."""
    m, p = args.model, args.parallel
    if m.hidden_size % m.num_attention_heads:
        raise ValueError("hidden_size must divide by num_attention_heads")
    if m.num_key_value_heads and m.num_attention_heads % m.num_key_value_heads:
        raise ValueError("heads must divide by kv heads")
    if p.config_mode == "global":
        need = p.pp_deg * max(p.global_tp_deg, 1) * max(p.global_cp_deg, 1)
        if world_size % max(need, 1):
            raise ValueError(
                f"world {world_size} not divisible by pp*tp*cp = {need}")
    if m.seq_length > m.max_position_embeddings:
        raise ValueError("seq_length exceeds max_position_embeddings")


def set_seed(seed: int) -> None:
    random.seed(seed)
    np.random.seed(seed)


class _CurrentStderrHandler(logging.StreamHandler):
    """A StreamHandler on whatever ``sys.stderr`` is when a record is
    emitted, not when the handler was built: the logger outlives
    redirections of stderr (a supervisor's log file, a test's capture)."""

    @property
    def stream(self):
        return sys.stderr

    @stream.setter
    def stream(self, value):  # StreamHandler.__init__ assigns it
        pass


def _make_logger(args: CoreArgs) -> logging.Logger:
    logger = logging.getLogger("hetu_galvatron_tpu")
    logger.propagate = False  # avoid double lines via the root logger
    if not logger.handlers:
        h = _CurrentStderrHandler()
        h.setFormatter(logging.Formatter("[%(levelname)s] %(message)s"))
        logger.addHandler(h)
    logger.setLevel(getattr(logging, args.logging.log_level.upper(),
                            logging.INFO))
    return logger


def _make_writers(args: CoreArgs):
    """TensorBoard / wandb writers when configured and importable
    (reference parallel_state.py:85-131; both are optional deps)."""
    tb = wb = None
    if args.logging.tensorboard_dir:
        try:
            from torch.utils.tensorboard import SummaryWriter

            tb = SummaryWriter(args.logging.tensorboard_dir)
        except ImportError:
            pass
    if args.logging.wandb_project:
        try:
            import wandb

            wb = wandb.init(project=args.logging.wandb_project,
                            config=args.model_dump())
        except ImportError:
            pass
    return tb, wb


def initialize_distributed(args: CoreArgs) -> bool:
    """Multi-host runtime init — the TPU-native leg of the reference's
    ``_initialize_distributed`` (runtime/initialize.py:114-160): where the
    reference reads torchrun's RANK/WORLD_SIZE and calls
    ``dist.init_process_group(nccl)``, a TPU pod joins the JAX coordination
    service (``jax.distributed.initialize``), after which ``jax.devices()``
    spans every host's chips and GSPMD collectives ride ICI/DCN.

    Triggered by parallel.num_processes > 1 (explicit) or the
    COORDINATOR_ADDRESS env (launcher-set); on Cloud TPU pods all arguments
    autodetect from the metadata service. Returns True when the
    coordination service was (already) initialized. Safe to call once per
    process; subsequent calls are no-ops.
    """
    import jax

    par = args.parallel
    env_addr = os.environ.get("COORDINATOR_ADDRESS")
    want = par.num_processes > 1 or env_addr is not None
    if not want:
        return False
    if jax.distributed.is_initialized():
        return True
    kwargs = {}
    addr = par.coordinator_address or env_addr
    if addr:
        kwargs["coordinator_address"] = addr
    # env mirrors every config field (NUM_PROCESSES/PROCESS_ID), so a
    # launcher can drive the whole handshake without touching the YAML
    nproc = par.num_processes
    if nproc <= 1 and os.environ.get("NUM_PROCESSES") is not None:
        nproc = int(os.environ["NUM_PROCESSES"])
    if nproc > 1:
        kwargs["num_processes"] = nproc
    pid = par.process_id
    if pid is None and os.environ.get("PROCESS_ID") is not None:
        pid = int(os.environ["PROCESS_ID"])
    if pid is not None:
        kwargs["process_id"] = pid
    jax.distributed.initialize(**kwargs)
    return True


def _world_of(num_devices: int, visible: int) -> int:
    """``parallel.num_devices`` (0 = every visible chip) checked against
    what the backend shows. Asking for more than is visible RAISES: a
    plan searched for 4 chips silently training on 1 is a different run."""
    if num_devices > visible:
        raise ValueError(
            f"parallel.num_devices={num_devices} but only {visible} "
            "device(s) are visible; fix the config or the host (0 = use "
            "every visible device)")
    return num_devices if num_devices > 0 else visible


def visible_world_size(args: CoreArgs) -> int:
    """The effective world size a run of ``args`` would see: every
    visible chip, or ``parallel.num_devices`` of them — the SAME
    derivation :func:`initialize` records in ``RunState.world_size``.
    Joins the coordination service first on multi-host pods (the backend
    must not be probed before ``jax.distributed.initialize``). THE
    helper for every pre-``initialize`` world probe (the elastic resume
    pre-pass, the supervisor's ``world_fn``), so the elastic trigger and
    the actual run state can never disagree about the world."""
    import jax

    initialize_distributed(args)
    return _world_of(args.parallel.num_devices, len(jax.devices()))


def initialize(args: CoreArgs, devices: Optional[List[Any]] = None
               ) -> RunState:
    """Validate + seed + discover devices; returns (and stores) the run
    state (reference initialize_galvatron, initialize.py:142-187 minus the
    process-group/NCCL legs)."""
    global _STATE
    import jax

    if devices is None:
        initialize_distributed(args)
    devices = list(devices if devices is not None else jax.devices())
    world = _world_of(args.parallel.num_devices, len(devices))
    validate_args(args, world)
    set_seed(args.train.seed)
    logger = _make_logger(args)
    tb, wb = _make_writers(args)
    state = RunState(args=args, devices=devices[:world], world_size=world,
                     logger=logger)
    state.tensorboard, state.wandb = tb, wb
    logger.info("initialized: %d device(s), platform %s, model %s",
                world, devices[0].platform, args.model.model_name)
    _STATE = state
    return state
