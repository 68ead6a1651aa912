#!/bin/sh
# Reward-trend learning checks. Each run prints per-episode rewards
# ("Rank-0: ... reward_env_N=R") at metric.log_level=1; compare the first fifth
# of episodes to the last. The figures in the comments are from CPU runs of
# earlier rounds; none has been repeated on the current code.
# CPU runs force JAX_PLATFORMS=cpu; drop it to run on an attached accelerator.
set -e
LOGS=${LOGS:-/tmp/sheeprl_tpu_learning}

# Recurrent PPO, CartPole (CPU, ~20 min): 13.6 -> 115.8 late avg, peak 398
JAX_PLATFORMS=cpu python -m sheeprl_tpu fabric=cpu exp=ppo_recurrent env=gym env.id=CartPole-v1 \
    env.num_envs=4 env.capture_video=False buffer.memmap=False \
    algo.total_steps=40960 algo.run_test=False checkpoint.save_last=False \
    metric.log_level=1 metric.log_every=2000 log_base_dir=$LOGS/rppo

# DroQ, Pendulum (CPU, ~15 min): -630 -> -139 mid avg, best episode -1.2
JAX_PLATFORMS=cpu python -m sheeprl_tpu fabric=cpu exp=droq env=gym env.id=Pendulum-v1 \
    env.num_envs=4 env.capture_video=False buffer.memmap=False \
    algo.total_steps=12000 algo.learning_starts=400 algo.run_test=False \
    checkpoint.save_last=False metric.log_level=1 metric.log_every=50000 \
    log_base_dir=$LOGS/droq

# Plain SAC, Pendulum (CPU, ~15 min)
JAX_PLATFORMS=cpu python -m sheeprl_tpu fabric=cpu exp=sac env=gym env.id=Pendulum-v1 \
    env.num_envs=4 env.capture_video=False buffer.memmap=False \
    algo.total_steps=12000 algo.learning_starts=400 algo.run_test=False \
    checkpoint.save_last=False metric.log_level=1 metric.log_every=50000 \
    log_base_dir=$LOGS/sac

# Decoupled SAC, Pendulum, 2 real jax.distributed procs (CPU, ~25 min) —
# the decoupled-topology learning run (round-5 row): player rewards trend
# while the trainer streams the actor back
python benchmarks/decoupled_learning_check.py --total-steps 12000 \
    --log-base-dir $LOGS/sac_decoupled

# Dreamer-V3, CartPole, round-2 recipe (TPU, ~25 min): 24.8 -> 150.6, peak 500
python -m sheeprl_tpu exp=dreamer_v3 env=gym env.id=CartPole-v1 \
    env.num_envs=4 env.capture_video=False buffer.memmap=False buffer.size=60000 \
    algo.total_steps=14336 algo.learning_starts=512 algo.replay_ratio=0.25 \
    algo.dense_units=64 algo.mlp_layers=1 \
    'algo.cnn_keys.encoder=[]' 'algo.mlp_keys.encoder=[state]' \
    'algo.cnn_keys.decoder=[]' 'algo.mlp_keys.decoder=[state]' \
    algo.run_test=False checkpoint.every=10000000 checkpoint.save_last=False \
    metric.log_level=1 metric.log_every=50000 log_base_dir=$LOGS/dv3_cartpole

# Dreamer-V1, PixelCatcher from pixels (TPU) — round-5 row: the DV1 recipe
# on the same toy pixel task (smaller nets than DV3; no discrete latents)
python -m sheeprl_tpu exp=dreamer_v1 env=pixel_catcher env.num_envs=4 \
    env.screen_size=32 env.capture_video=False buffer.memmap=False buffer.size=60000 \
    algo.total_steps=30720 algo.learning_starts=1024 \
    algo.dense_units=128 algo.mlp_layers=1 \
    algo.world_model.stochastic_size=32 \
    algo.world_model.encoder.cnn_channels_multiplier=8 \
    algo.world_model.recurrent_model.recurrent_state_size=128 \
    'algo.cnn_keys.encoder=[rgb]' 'algo.mlp_keys.encoder=[]' \
    algo.run_test=False checkpoint.every=10000000 checkpoint.save_last=False \
    metric.log_level=1 metric.log_every=4000 log_base_dir=$LOGS/dv1_pixel

# Dreamer-V2, PixelCatcher from pixels (TPU) — round-5 row
python -m sheeprl_tpu exp=dreamer_v2 env=pixel_catcher env.num_envs=4 \
    env.screen_size=32 env.capture_video=False buffer.memmap=False buffer.size=60000 \
    algo.total_steps=30720 algo.learning_starts=1024 \
    algo.dense_units=128 algo.mlp_layers=1 \
    algo.world_model.discrete_size=16 algo.world_model.stochastic_size=16 \
    algo.world_model.encoder.cnn_channels_multiplier=8 \
    algo.world_model.recurrent_model.recurrent_state_size=128 \
    'algo.cnn_keys.encoder=[rgb]' 'algo.mlp_keys.encoder=[]' \
    algo.run_test=False checkpoint.every=10000000 checkpoint.save_last=False \
    metric.log_level=1 metric.log_every=4000 log_base_dir=$LOGS/dv2_pixel

# Dreamer-V3, PixelCatcher from pixels (TPU, ~65 min): -0.02 -> 12.0 (solved)
python -m sheeprl_tpu exp=dreamer_v3 env=pixel_catcher env.num_envs=4 \
    env.screen_size=32 env.capture_video=False buffer.memmap=False buffer.size=60000 \
    algo.total_steps=30720 algo.learning_starts=1024 algo.replay_ratio=0.5 \
    algo.dense_units=128 algo.mlp_layers=1 \
    algo.world_model.discrete_size=16 algo.world_model.stochastic_size=16 \
    algo.world_model.encoder.cnn_channels_multiplier=8 \
    algo.world_model.recurrent_model.recurrent_state_size=128 \
    algo.world_model.transition_model.hidden_size=128 \
    algo.world_model.representation_model.hidden_size=128 \
    'algo.cnn_keys.encoder=[rgb]' 'algo.mlp_keys.encoder=[]' \
    algo.run_test=False checkpoint.every=10000000 checkpoint.save_last=False \
    metric.log_level=1 metric.log_every=4000 log_base_dir=$LOGS/dv3_pixel
