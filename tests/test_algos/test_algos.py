"""End-to-end 1-iteration runs of every registered algorithm on CPU with the
dummy envs — the integration backbone (reference tests/test_algos/test_algos.py,
566 LoC: one test per algo, dry_run=True, tiny sizes, 2 envs)."""
import pytest

from sheeprl_tpu.cli import run


def _run(args, standard_args):
    run(args + standard_args)


@pytest.mark.parametrize(
    "env_id",
    [
        "discrete_dummy",
        pytest.param("multidiscrete_dummy", marks=pytest.mark.full),
        pytest.param("continuous_dummy", marks=pytest.mark.full),
    ],
)
def test_ppo(standard_args, env_id):
    _run(
        [
            "exp=ppo",
            "env=dummy",
            f"env.id={env_id}",
            "algo.rollout_steps=8",
            "algo.per_rank_batch_size=4",
            "algo.update_epochs=1",
            "algo.mlp_keys.encoder=[state]",
            "algo.cnn_keys.encoder=[rgb]",
            "algo.encoder.cnn_features_dim=16",
            "algo.dense_units=8",
            "algo.mlp_layers=1",
        ],
        standard_args,
    )


@pytest.mark.parametrize(
    "device_cache, n_devices",
    [
        ("auto", 1),
        ("true", 1),
        # devices=2 forces the dp-SHARDED uniform ring (per-device env
        # blocks, batches assembled pre-sharded P(None, "dp"))
        pytest.param("true", 2, id="true-sharded"),
    ],
)
def test_sac(standard_args, device_cache, n_devices):
    _run(
        [
            "exp=sac",
            "env=dummy",
            "env.id=continuous_dummy",
            f"fabric.devices={n_devices}",
            "algo.per_rank_batch_size=4",
            "algo.hidden_size=8",
            "algo.learning_starts=0",
            "algo.mlp_keys.encoder=[state]",
            "buffer.size=64",
            f"buffer.device_cache={device_cache}",  # true forces the HBM ring
        ],
        standard_args,
    )


@pytest.mark.parametrize(
    "env_id",
    [
        "discrete_dummy",
        pytest.param("multidiscrete_dummy", marks=pytest.mark.full),
        pytest.param("continuous_dummy", marks=pytest.mark.full),
    ],
)
def test_dreamer_v3(standard_args, env_id):
    _run(
        [
            "exp=dreamer_v3",
            "env=dummy",
            f"env.id={env_id}",
            "algo=dreamer_v3_XS",
            "algo.per_rank_batch_size=2",
            "algo.per_rank_sequence_length=2",
            "algo.learning_starts=0",
            "algo.horizon=4",
            "algo.dense_units=16",
            "algo.world_model.encoder.cnn_channels_multiplier=2",
            "algo.world_model.recurrent_model.recurrent_state_size=16",
            "algo.world_model.transition_model.hidden_size=16",
            "algo.world_model.representation_model.hidden_size=16",
            "algo.world_model.discrete_size=4",
            "algo.world_model.stochastic_size=4",
            "algo.cnn_keys.encoder=[rgb]",
            "algo.mlp_keys.encoder=[state]",
            "buffer.size=64",
        ],
        standard_args,
    )


# DreamerV3-XS at toy widths on the dummy env, with the device ring forced on
DV3_RING_ARGS = [
    "exp=dreamer_v3",
    "env=dummy",
    "env.id=discrete_dummy",
    "algo=dreamer_v3_XS",
    "algo.per_rank_batch_size=2",
    "algo.per_rank_sequence_length=2",
    "algo.learning_starts=0",
    "algo.horizon=4",
    "algo.dense_units=16",
    "algo.world_model.encoder.cnn_channels_multiplier=2",
    "algo.world_model.recurrent_model.recurrent_state_size=16",
    "algo.world_model.transition_model.hidden_size=16",
    "algo.world_model.representation_model.hidden_size=16",
    "algo.world_model.discrete_size=4",
    "algo.world_model.stochastic_size=4",
    "algo.cnn_keys.encoder=[rgb]",
    "algo.mlp_keys.encoder=[state]",
    "buffer.size=64",
    "buffer.device_cache=true",
]


def test_dreamer_v3_device_ring(standard_args, devices):
    """HBM-resident replay ring (buffer.device_cache=true forces it on the
    CPU backend): the bench-critical path where batches gather on device.
    devices=2 exercises the dp-SHARDED ring (per-device env sub-rings,
    batches assembled pre-sharded — VERDICT r4 #3)."""
    _run([f"fabric.devices={devices}"] + DV3_RING_ARGS, standard_args)


def test_dreamer_v3_device_ring_emits_its_layout_once(standard_args):
    """The run's `ring_layout` event: one per ring allocated, valid against
    the schema, with the 64x64x3 uint8 frames stored row-contiguous as
    [96, 128] and everything else (vectors, the action, four scalars) in
    its own shape."""
    import glob
    import json

    from sheeprl_tpu.telemetry import validate_jsonl

    run(DV3_RING_ARGS + standard_args + ["metric.log_level=1", "metric.log_every=1000"])
    streams = glob.glob("logs/runs/**/telemetry.jsonl", recursive=True)
    assert len(streams) == 1, streams
    assert validate_jsonl(streams[0]) == []
    events = [json.loads(line) for line in open(streams[0])]
    layouts = [e for e in events if e["event"] == "ring_layout"]
    assert len(layouts) == 1, [e["event"] for e in events]
    (ev,) = layouts
    assert ev["rows"] == 64 and ev["n_envs"] == 2 and ev["device"].startswith("cpu:")
    rgb = ev["keys"]["rgb"]
    assert rgb == {"logical": [64, 64, 3], "stored": [96, 128], "dtype": "uint8", "bytes": 64 * 2 * 12288}
    assert all(v["stored"] == v["logical"] for k, v in ev["keys"].items() if k != "rgb")
    assert {"actions", "rewards", "terminated", "truncated", "is_first", "state"} <= set(ev["keys"])
    assert ev["total_bytes"] == sum(v["bytes"] for v in ev["keys"].values())
    assert ev["contiguous_bytes"] == rgb["bytes"]
    assert 0.99 < ev["contiguous_bytes_share"] < 1.0
    # the placement event it stands beside comes first: set-up, then the first sync
    order = [e["event"] for e in events]
    assert order.index("placement") < order.index("ring_layout")


@pytest.mark.parametrize("rssm, kernels", [("coupled", 6), ("decoupled", 4)])
def test_dreamer_v3_emits_what_its_sequence_scan_hoists_once(standard_args, rssm, kernels):
    """The run's `wgrad_hoist` event: one, when the train function is traced,
    valid against the schema, with the kernels the world model's scan applies
    (GRU, pre-GRU MLP, both layers of the transition head and, coupled, of the
    representation head) and the T*B rows of their contractions."""
    import glob
    import json

    from sheeprl_tpu.telemetry import validate_jsonl

    args = [a for a in DV3_RING_ARGS if not a.startswith("buffer.device_cache")]
    run(args + standard_args + [f"algo.world_model.decoupled_rssm={rssm == 'decoupled'}", "metric.log_level=1", "metric.log_every=1000"])
    streams = glob.glob("logs/runs/**/telemetry.jsonl", recursive=True)
    assert len(streams) == 1, streams
    assert validate_jsonl(streams[0]) == []
    events = [json.loads(line) for line in open(streams[0])]
    hoists = [e for e in events if e["event"] == "wgrad_hoist"]
    assert len(hoists) == 1, [e["event"] for e in events]
    (ev,) = hoists
    assert ev["scan"] == rssm and ev["kernels"] == kernels and ev["rows"] == 2 * 2
    gru = 4 * (16 + 16) * 3 * 16  # [dense_units + recurrent_state_size, 3 x recurrent_state_size] float32
    assert ev["kernel_bytes"] > gru


@pytest.mark.parametrize("g", [1, 3])
def test_dreamer_v3_burst_keys_are_the_eager_splits(g):
    """One dispatch in place of three on the learner's chain to the train
    step: the key stream of a run must not move by it."""
    import jax
    import numpy as np

    from sheeprl_tpu.algos.dreamer_v3.dreamer_v3 import burst_keys

    root = jax.random.key(11)
    for _ in range(2):  # the second call takes the first one's output
        want_root, sub = jax.random.split(root)
        want = jax.random.split(sub, g)
        root, keys = burst_keys(root, g)
        assert keys.shape == (g,)
        np.testing.assert_array_equal(jax.random.key_data(root), jax.random.key_data(want_root))
        np.testing.assert_array_equal(jax.random.key_data(keys), jax.random.key_data(want))


def test_dreamer_v3_decoupled_rssm(standard_args):
    """DecoupledRSSM variant: posterior computed from embeddings alone
    (reference agent.py:501-593, dreamer_v3.py:115-129)."""
    _run(
        [
            "exp=dreamer_v3",
            "env=dummy",
            "env.id=discrete_dummy",
            "algo=dreamer_v3_XS",
            "algo.world_model.decoupled_rssm=True",
            "algo.per_rank_batch_size=2",
            "algo.per_rank_sequence_length=2",
            "algo.learning_starts=0",
            "algo.horizon=4",
            "algo.dense_units=16",
            "algo.world_model.encoder.cnn_channels_multiplier=2",
            "algo.world_model.recurrent_model.recurrent_state_size=16",
            "algo.world_model.transition_model.hidden_size=16",
            "algo.world_model.representation_model.hidden_size=16",
            "algo.world_model.discrete_size=4",
            "algo.world_model.stochastic_size=4",
            "algo.cnn_keys.encoder=[rgb]",
            "algo.mlp_keys.encoder=[state]",
            "buffer.size=64",
        ],
        standard_args,
    )


def test_dreamer_v2_episode_buffer_memmap(standard_args):
    """Episode buffer with memmap=True: committed episodes live on disk
    inside a real training loop (EpisodeBuffer._memmap_episode path)."""
    args = [a for a in standard_args if not a.startswith("buffer.memmap")]
    _run(
        [
            "exp=dreamer_v2",
            "env=dummy",
            "env.id=discrete_dummy",
            "buffer.type=episode",
            "buffer.memmap=True",
            "algo.per_rank_batch_size=2",
            "algo.per_rank_sequence_length=2",
            "algo.per_rank_pretrain_steps=1",
            "algo.learning_starts=0",
            "algo.horizon=4",
            "algo.dense_units=8",
            "algo.mlp_layers=1",
            "algo.world_model.encoder.cnn_channels_multiplier=2",
            "algo.world_model.recurrent_model.recurrent_state_size=16",
            "algo.world_model.transition_model.hidden_size=16",
            "algo.world_model.representation_model.hidden_size=16",
            "algo.world_model.discrete_size=4",
            "algo.world_model.stochastic_size=4",
            "algo.cnn_keys.encoder=[rgb]",
            "algo.mlp_keys.encoder=[state]",
            "buffer.size=64",
        ],
        args,
    )


@pytest.mark.parametrize(
    "env_id,buffer_type,distribution",
    [
        ("discrete_dummy", "sequential", "auto"),
        pytest.param("discrete_dummy", "episode", "auto", marks=pytest.mark.full),
        pytest.param("multidiscrete_dummy", "sequential", "auto", marks=pytest.mark.full),
        pytest.param("multidiscrete_dummy", "episode", "auto", marks=pytest.mark.full),
        pytest.param("continuous_dummy", "sequential", "auto", marks=pytest.mark.full),
        pytest.param("continuous_dummy", "episode", "auto", marks=pytest.mark.full),
        pytest.param("continuous_dummy", "sequential", "tanh_normal", marks=pytest.mark.full),
    ],
)
def test_dreamer_v2(standard_args, env_id, buffer_type, distribution):
    _run(
        [
            "exp=dreamer_v2",
            "env=dummy",
            f"env.id={env_id}",
            f"buffer.type={buffer_type}",
            f"distribution.type={distribution}",
            "algo.per_rank_batch_size=2",
            "algo.per_rank_sequence_length=2",
            "algo.per_rank_pretrain_steps=1",
            "algo.learning_starts=0",
            "algo.horizon=4",
            "algo.dense_units=8",
            "algo.mlp_layers=1",
            "algo.world_model.encoder.cnn_channels_multiplier=2",
            "algo.world_model.recurrent_model.recurrent_state_size=16",
            "algo.world_model.transition_model.hidden_size=16",
            "algo.world_model.representation_model.hidden_size=16",
            "algo.world_model.discrete_size=4",
            "algo.world_model.stochastic_size=4",
            "algo.cnn_keys.encoder=[rgb]",
            "algo.mlp_keys.encoder=[state]",
            "buffer.size=64",
        ],
        standard_args,
    )


@pytest.mark.parametrize(
    "env_id",
    [
        "discrete_dummy",
        pytest.param("multidiscrete_dummy", marks=pytest.mark.full),
        pytest.param("continuous_dummy", marks=pytest.mark.full),
    ],
)
def test_ppo_recurrent(standard_args, env_id):
    _run(
        [
            "exp=ppo_recurrent",
            "env=dummy",
            f"env.id={env_id}",
            "algo.rollout_steps=8",
            "algo.per_rank_sequence_length=4",
            "algo.per_rank_num_batches=2",
            "algo.update_epochs=1",
            "algo.mlp_keys.encoder=[state]",
            "algo.cnn_keys.encoder=[rgb]",
            "algo.encoder.cnn_features_dim=16",
            "algo.dense_units=8",
            "algo.rnn.lstm.hidden_size=8",
            "algo.mlp_layers=1",
        ],
        standard_args,
    )


@pytest.mark.parametrize(
    "env_id",
    [
        "discrete_dummy",
        pytest.param("multidiscrete_dummy", marks=pytest.mark.full),
        pytest.param("continuous_dummy", marks=pytest.mark.full),
    ],
)
def test_dreamer_v1(standard_args, env_id):
    _run(
        [
            "exp=dreamer_v1",
            "env=dummy",
            f"env.id={env_id}",
            "algo.per_rank_batch_size=2",
            "algo.per_rank_sequence_length=2",
            "algo.learning_starts=0",
            "algo.horizon=4",
            "algo.dense_units=8",
            "algo.mlp_layers=1",
            "algo.world_model.encoder.cnn_channels_multiplier=2",
            "algo.world_model.recurrent_model.recurrent_state_size=16",
            "algo.world_model.transition_model.hidden_size=16",
            "algo.world_model.representation_model.hidden_size=16",
            "algo.world_model.stochastic_size=4",
            "algo.cnn_keys.encoder=[rgb]",
            "algo.mlp_keys.encoder=[state]",
            "buffer.size=64",
        ],
        standard_args,
    )


@pytest.mark.parametrize(
    "env_id",
    ["discrete_dummy", pytest.param("continuous_dummy", marks=pytest.mark.full)],
)
def test_p2e_dv1(standard_args, env_id, tmp_path):
    """Exploration then finetuning from its checkpoint (reference
    test_algos.py:262-338)."""
    import glob
    import os

    tiny = [
        "env=dummy",
        f"env.id={env_id}",
        "algo.per_rank_batch_size=2",
        "algo.per_rank_sequence_length=2",
        "algo.learning_starts=0",
        "algo.horizon=4",
        "algo.dense_units=8",
        "algo.mlp_layers=1",
        "algo.ensembles.n=3",
        "algo.world_model.encoder.cnn_channels_multiplier=2",
        "algo.world_model.recurrent_model.recurrent_state_size=16",
        "algo.world_model.representation_model.hidden_size=16",
        "algo.world_model.transition_model.hidden_size=16",
        "algo.world_model.stochastic_size=4",
        "algo.cnn_keys.encoder=[rgb]",
        "algo.mlp_keys.encoder=[state]",
        "buffer.size=64",
        "root_dir=p2e_test",
        "run_name=expl",
    ]
    expl_args = [a for a in standard_args if "save_last" not in a] + [
        "checkpoint.save_last=True",
        "buffer.checkpoint=True",
    ]
    _run(["exp=p2e_dv1_exploration"] + tiny, expl_args)
    ckpts = sorted(glob.glob(os.path.join("logs", "runs", "p2e_test", "expl", "*", "checkpoint", "*.ckpt")))
    assert ckpts, "no exploration checkpoint written"
    _run(
        ["exp=p2e_dv1_finetuning", f"checkpoint.exploration_ckpt_path={ckpts[-1]}"] + tiny,
        standard_args,
    )


@pytest.mark.parametrize(
    "env_id",
    ["discrete_dummy", pytest.param("continuous_dummy", marks=pytest.mark.full)],
)
def test_p2e_dv3(standard_args, env_id, tmp_path):
    import glob
    import os

    tiny = [
        "env=dummy",
        f"env.id={env_id}",
        "algo=p2e_dv3",
        "algo.per_rank_batch_size=2",
        "algo.per_rank_sequence_length=2",
        "algo.learning_starts=0",
        "algo.horizon=4",
        "algo.dense_units=8",
        "algo.mlp_layers=1",
        "algo.ensembles.n=3",
        "algo.world_model.encoder.cnn_channels_multiplier=2",
        "algo.world_model.recurrent_model.recurrent_state_size=16",
        "algo.world_model.representation_model.hidden_size=16",
        "algo.world_model.transition_model.hidden_size=16",
        "algo.world_model.discrete_size=4",
        "algo.world_model.stochastic_size=4",
        "algo.cnn_keys.encoder=[rgb]",
        "algo.mlp_keys.encoder=[state]",
        "buffer.size=64",
        "root_dir=p2e_dv3_test",
        "run_name=expl",
    ]
    expl_args = [a for a in standard_args if "save_last" not in a] + [
        "checkpoint.save_last=True",
        "buffer.checkpoint=True",
    ]
    _run(["exp=p2e_dv3_exploration", "algo.name=p2e_dv3_exploration"] + tiny, expl_args)
    ckpts = sorted(
        glob.glob(os.path.join("logs", "runs", "p2e_dv3_test", "expl", "*", "checkpoint", "*.ckpt"))
    )
    assert ckpts, "no exploration checkpoint written"
    _run(
        [
            "exp=p2e_dv3_finetuning",
            "algo.name=p2e_dv3_finetuning",
            f"checkpoint.exploration_ckpt_path={ckpts[-1]}",
        ]
        + tiny,
        standard_args,
    )


@pytest.mark.parametrize(
    "env_id",
    ["discrete_dummy", pytest.param("continuous_dummy", marks=pytest.mark.full)],
)
def test_p2e_dv2(standard_args, env_id, tmp_path):
    import glob
    import os

    tiny = [
        "env=dummy",
        f"env.id={env_id}",
        "algo=p2e_dv2",
        "algo.per_rank_batch_size=2",
        "algo.per_rank_sequence_length=2",
        "algo.learning_starts=0",
        "algo.horizon=4",
        "algo.dense_units=8",
        "algo.mlp_layers=1",
        "algo.ensembles.n=3",
        "algo.world_model.encoder.cnn_channels_multiplier=2",
        "algo.world_model.recurrent_model.recurrent_state_size=16",
        "algo.world_model.representation_model.hidden_size=16",
        "algo.world_model.transition_model.hidden_size=16",
        "algo.world_model.discrete_size=4",
        "algo.world_model.stochastic_size=4",
        "algo.cnn_keys.encoder=[rgb]",
        "algo.mlp_keys.encoder=[state]",
        "buffer.size=64",
        "root_dir=p2e_dv2_test",
        "run_name=expl",
    ]
    expl_args = [a for a in standard_args if "save_last" not in a] + [
        "checkpoint.save_last=True",
        "buffer.checkpoint=True",
    ]
    _run(["exp=p2e_dv2_exploration", "algo.name=p2e_dv2_exploration"] + tiny, expl_args)
    ckpts = sorted(
        glob.glob(os.path.join("logs", "runs", "p2e_dv2_test", "expl", "*", "checkpoint", "*.ckpt"))
    )
    assert ckpts, "no exploration checkpoint written"
    _run(
        [
            "exp=p2e_dv2_finetuning",
            "algo.name=p2e_dv2_finetuning",
            f"checkpoint.exploration_ckpt_path={ckpts[-1]}",
        ]
        + tiny,
        standard_args,
    )


@pytest.mark.full
def test_p2e_dv3_bf16_mixed(standard_args):
    """The most complex train fn (multi-critic P2E exploration) stays
    finite under fabric.precision=bf16-mixed (the doapp recipes' setting)."""
    _run(
        [
            "exp=p2e_dv3_exploration",
            "algo.name=p2e_dv3_exploration",
            "algo=p2e_dv3",
            "env=dummy",
            "env.id=discrete_dummy",
            "fabric.precision=bf16-mixed",
            "algo.per_rank_batch_size=2",
            "algo.per_rank_sequence_length=2",
            "algo.learning_starts=0",
            "algo.horizon=4",
            "algo.dense_units=8",
            "algo.mlp_layers=1",
            "algo.ensembles.n=3",
            "algo.world_model.encoder.cnn_channels_multiplier=2",
            "algo.world_model.recurrent_model.recurrent_state_size=16",
            "algo.world_model.representation_model.hidden_size=16",
            "algo.world_model.transition_model.hidden_size=16",
            "algo.world_model.discrete_size=4",
            "algo.world_model.stochastic_size=4",
            "algo.cnn_keys.encoder=[rgb]",
            "algo.mlp_keys.encoder=[state]",
            "buffer.size=64",
        ],
        standard_args,
    )


def test_ppo_decoupled(standard_args):
    common = [
        "exp=ppo_decoupled",
        "env=dummy",
        "env.id=discrete_dummy",
        "algo.rollout_steps=8",
        "algo.per_rank_batch_size=4",
        "algo.update_epochs=1",
        "algo.mlp_keys.encoder=[state]",
        "algo.cnn_keys.encoder=[]",
        "algo.dense_units=8",
        "algo.mlp_layers=1",
    ]
    # a decoupled run needs at least a player and a trainer device
    # (reference test_algos.py:126-144 asserts the same failure)
    with pytest.raises(RuntimeError):
        _run(common + ["fabric.devices=1"], standard_args)
    _run(common + ["fabric.devices=2"], standard_args)


def test_sac_decoupled(standard_args):
    common = [
        "exp=sac_decoupled",
        "env=dummy",
        "env.id=continuous_dummy",
        "algo.per_rank_batch_size=4",
        "algo.hidden_size=8",
        "algo.learning_starts=0",
        "algo.mlp_keys.encoder=[state]",
        "buffer.size=64",
    ]
    with pytest.raises(RuntimeError):
        _run(common + ["fabric.devices=1"], standard_args)
    _run(common + ["fabric.devices=2"], standard_args)


def test_sac_ae(standard_args):
    _run(
        [
            "exp=sac_ae",
            "env=dummy",
            "env.id=continuous_dummy",
            "algo.per_rank_batch_size=4",
            "algo.hidden_size=8",
            "algo.dense_units=8",
            "algo.cnn_channels_multiplier=1",
            "algo.encoder.features_dim=8",
            "algo.learning_starts=0",
            "algo.cnn_keys.encoder=[rgb]",
            "algo.mlp_keys.encoder=[state]",
            "buffer.size=16",
        ],
        standard_args,
    )


def test_droq(standard_args):
    _run(
        [
            "exp=droq",
            "env=dummy",
            "env.id=continuous_dummy",
            "algo.per_rank_batch_size=4",
            "algo.hidden_size=8",
            "algo.learning_starts=0",
            "algo.mlp_keys.encoder=[state]",
            "buffer.size=64",
        ],
        standard_args,
    )


@pytest.mark.parametrize(
    "env_id",
    ["discrete_dummy", pytest.param("continuous_dummy", marks=pytest.mark.full)],
)
def test_a2c(standard_args, env_id):
    _run(
        [
            "exp=a2c",
            "env=dummy",
            f"env.id={env_id}",
            "algo.rollout_steps=4",
            "algo.mlp_keys.encoder=[state]",
            "algo.dense_units=8",
            "algo.mlp_layers=1",
        ],
        standard_args,
    )
