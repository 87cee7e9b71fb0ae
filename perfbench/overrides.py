"""Overrides every cell gets, and the ones only the CPU rehearsal adds."""

# what keeps a run short and the checkout clean; none of it is in the steady loop
COMMON_OVERRIDES = [
    "buffer.checkpoint=False",
    "checkpoint.save_last=False",
    "checkpoint.every=1000000000",
    "algo.run_test=False",
    "model_manager.disabled=True",
    "algo.total_steps=1000000000",
    "algo.max_wall_time_s=330",  # a net under the run; the window closes it far sooner
]
# the CPU rehearsal only: the same program at widths a CPU compiles in seconds
REHEARSAL_OVERRIDES = [
    "algo.per_rank_batch_size=4",
    "algo.per_rank_sequence_length=8",
    "algo.horizon=3",
    "algo.dense_units=16",
    "algo.mlp_layers=2",
    "algo.world_model.encoder.cnn_channels_multiplier=2",
    "algo.world_model.recurrent_model.recurrent_state_size=8",
    "algo.world_model.transition_model.hidden_size=16",
    "algo.world_model.representation_model.hidden_size=16",
    "algo.world_model.discrete_size=4",
    "algo.world_model.stochastic_size=4",
    "buffer.size=4096",
    "buffer.device_cache=true",
    "algo.learning_starts=128",
]
