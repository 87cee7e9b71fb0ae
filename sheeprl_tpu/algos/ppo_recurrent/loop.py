"""The run around recurrent PPO's rollout-and-update loop, shared by its two
backbones (`ppo_recurrent.main`: the LSTM; `sequence_policy.main`: a sequence
model): seeding, the log directory, the envs and the resumed state; telemetry,
checkpoints and the guard; the counters a checkpoint keeps; what follows every
update (the cadence of logs and checkpoints, the stop) and the close. What
differs per backbone stays in the two loops: acting, what a rollout records,
and the update's data.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Tuple

from ...config import Config
from ...parallel import Distributed
from ...resilience import RunGuard
from ...telemetry import Telemetry
from ...utils.checkpoint import CheckpointManager
from ...utils.env import vectorize
from ...utils.logger import get_log_dir, get_logger
from ...utils.utils import save_configs
from .utils import AGGREGATOR_KEYS

Learner = Callable[[], Tuple[Any, Any, Any]]  # the loop's newest (params, opt_state, rng)


class LoopRun:
    def __init__(self, dist: Distributed, cfg: Config):
        self.cfg = cfg
        self.root_key = dist.seed_everything(cfg.seed)
        self.rank = dist.process_index
        self.log_dir = get_log_dir(cfg, cfg.root_dir, cfg.run_name)
        self.logger = get_logger(cfg, self.log_dir, self.rank)
        if self.rank == 0:
            save_configs(cfg, self.log_dir)
        self.envs = vectorize(cfg, cfg.seed, self.rank, self.log_dir)
        self.state: Optional[Dict[str, Any]] = CheckpointManager.load(cfg.checkpoint.resume_from) if cfg.checkpoint.resume_from else None
        if self.state:
            self.root_key = self.state["rng"]

    def begin(self, policy_steps_per_iter: int) -> None:
        """Telemetry, checkpoints and the guard, then the counters: a resumed run goes on where its checkpoint stood."""
        cfg, state = self.cfg, self.state
        self.telem = Telemetry.setup(cfg, self.log_dir, self.rank, logger=self.logger, aggregator_keys=AGGREGATOR_KEYS)
        ckpt = CheckpointManager(self.log_dir, keep_last=cfg.checkpoint.keep_last, enabled=self.rank == 0)
        self.guard = RunGuard.setup(cfg, ckpt, self.telem, self.log_dir)
        self.ckpt = self.guard.ckpt
        self.num_updates = int(cfg.algo.total_steps) // policy_steps_per_iter if not cfg.dry_run else 1
        self.start_iter = (state["update"] + 1) if state else 1
        self.update_iter = self.start_iter - 1
        self.policy_step = state["policy_step"] if state else 0
        self.last_log = state["last_log"] if state else 0
        self.last_checkpoint = state["last_checkpoint"] if state else 0

    def _ckpt_state(self, learner: Learner) -> Callable[[], Dict[str, Any]]:
        def state() -> Dict[str, Any]:
            params, opt_state, rng = learner()
            return {"params": params, "opt_state": opt_state, "update": self.update_iter, "policy_step": self.policy_step,
                    "last_log": self.last_log, "last_checkpoint": self.last_checkpoint, "rng": rng}

        return state

    def end_iteration(self, update_iter: int, learner: Learner) -> bool:
        """After an update: the log and the checkpoint where their cadence is due; whether the run stops here."""
        cfg = self.cfg
        self.update_iter = update_iter
        if self.policy_step - self.last_log >= cfg.metric.log_every or cfg.dry_run:
            self.telem.log(self.policy_step)
            self.last_log = self.policy_step
        if (cfg.checkpoint.every > 0 and self.policy_step - self.last_checkpoint >= cfg.checkpoint.every) or cfg.dry_run \
                or update_iter == self.num_updates:
            self.last_checkpoint = self.policy_step
            self.ckpt.save(self.policy_step, self._ckpt_state(learner)())
        return self.guard.stop_reached(self.policy_step, int(cfg.algo.total_steps), self._ckpt_state(learner))

    def close(self, learner: Learner, test: Optional[Callable[[Any], None]] = None) -> None:
        """`test(params)`, where given, runs on rank 0 between the telemetry's close and the model's registration."""
        self.guard.close(self.policy_step, self._ckpt_state(learner))
        self.envs.close()
        self.telem.close(self.policy_step)
        params = learner()[0]
        if self.rank == 0 and test is not None:
            test(params)
        if self.rank == 0 and not self.cfg.model_manager.disabled:
            from ...utils.model_manager import register_model

            register_model(self.cfg, {"agent": params}, self.log_dir)
        if self.logger is not None:
            self.logger.close()
