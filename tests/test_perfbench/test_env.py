"""The benchmark's seeded environment and env 0's clock, and the judging of
compared numbers: nothing here needs a run."""

import json

import numpy as np
import pytest

from perfbench import correct, env as bench_env

SPEC = {
    "frame": [64, 64, 3],
    "action": {"type": "continuous", "dim": 6},
    "reward": {"values": [0.0, 1.0], "probs": [0.5, 0.5]},
    "episode_frames": {"low": 4, "high": 12, "count": 5, "multiple_of": 2},
    "episode_end": "truncated",
}


def _play(env, steps):
    obs, _ = env.reset()
    frames, ends = [obs["rgb"]], []
    for i in range(steps):
        obs, reward, terminated, truncated, _ = env.step(env.action_space.sample())
        frames.append(obs["rgb"])
        if terminated or truncated:
            ends.append((i + 1, bool(terminated), bool(truncated)))
            env.reset()
    return np.stack(frames), ends


def test_the_same_seed_gives_the_same_frames_and_another_gives_others():
    a, _ = _play(bench_env.make("x", json.dumps(SPEC), seed=7, rank=0), 5)
    b, _ = _play(bench_env.make("x", SPEC, seed=7, rank=0), 5)
    c, _ = _play(bench_env.make("x", SPEC, seed=8, rank=0), 5)
    assert a.dtype == np.uint8 and a.shape == (6, 64, 64, 3)
    assert (a == b).all() and (a != c).any()


def test_every_seed_has_the_same_set_of_episode_lengths_in_another_order():
    orders = [bench_env.episode_lengths(SPEC, seed, 0).tolist() for seed in range(8)]
    assert all(sorted(o) == [4, 6, 8, 10, 12] for o in orders)
    assert len({tuple(o) for o in orders}) > 1


@pytest.mark.parametrize("end,flags", [("truncated", (False, True)), ("terminated", (True, False))])
def test_an_episode_ends_after_its_drawn_length_as_the_real_env_would(end, flags):
    spec = {**SPEC, "episode_end": end}
    lengths = bench_env.episode_lengths(spec, 3, 0)
    _, ends = _play(bench_env.make("x", spec, seed=3, rank=0), int(lengths[0] + lengths[1]))
    assert ends == [(int(lengths[0]), *flags), (int(lengths[0] + lengths[1]), *flags)]


def test_the_first_episode_can_have_a_length_of_its_own():
    spec = {**SPEC, "episode_frames": {"low": 10, "high": 10, "count": 1, "multiple_of": 2, "first": 14}}
    _, ends = _play(bench_env.make("x", spec, seed=1, rank=0), 34)
    assert [e[0] for e in ends] == [14, 24, 34]


def test_a_discrete_action_space_and_an_unknown_one():
    env = bench_env.make("x", {**SPEC, "action": {"type": "discrete", "dim": 17}})
    assert env.action_space.n == 17
    with pytest.raises(ValueError):
        bench_env.make("x", {**SPEC, "action": {"type": "box", "dim": 1}})


def test_env_0_alone_stamps_entry_and_exit_of_every_step(tmp_path):
    path = str(tmp_path / "stamps.i64")
    bench_env.create_stamps(path)
    # factory.py hands env i the seed plus i and the rank i
    _play(bench_env.make("x", SPEC, seed=5 + 1, rank=1, stamps=path), 3)
    assert int(bench_env.open_stamps(path)[0]) == 0
    _play(bench_env.make("x", SPEC, seed=5, rank=0, stamps=path), 3)
    stamps = bench_env.open_stamps(path)
    assert int(stamps[0]) == 3
    pairs = np.asarray(stamps[1:7]).reshape(3, 2)
    assert (pairs[:, 1] >= pairs[:, 0]).all() and (pairs[1:, 0] >= pairs[:-1, 1]).all()


def test_a_number_without_a_limit_is_not_compared_and_a_limit_without_a_number_fails():
    numbers = {"first_grad": 0.02, "wm_loss": 5.0}
    assert correct.judge(numbers, {"first_grad": 0.1}) == {"first_grad": {"value": 0.02, "limit": 0.1, "ok": True}}
    judged = correct.judge(numbers, {"first_grad": 0.01, "player_h": 0.1})
    assert judged["first_grad"]["ok"] is False and judged["player_h"] == {"value": None, "limit": 0.1, "ok": False}
    assert correct.judge({"change": float("nan")}, {"change": 1.0})["change"]["ok"] is False


# --------------------------------------------------------------------------- #
# what the ring gives back, against what the environments produced
# --------------------------------------------------------------------------- #

RING_CFG = {
    "algo": {"num_envs": 2, "action_repeat": 2},
    "env": {**SPEC, "episode_frames": {"low": 6, "high": 6, "count": 1, "multiple_of": 2}},
}


def _ring(tmp_path, steps=24):
    """Two envs played for ``steps`` frames under action repeat 2, and the
    rows a sound ring would give back of each."""
    path = str(tmp_path / "stamps.i64")
    bench_env.create_stamps(path)
    rng = np.random.default_rng(0)
    for rank in range(2):
        env = bench_env.make("x", RING_CFG["env"], seed=9 + rank, rank=rank, stamps=path)
        env.reset()
        for i in range(steps // 2):
            action = rng.uniform(-1, 1, 6).astype(np.float32)
            for _ in range(2):
                _, _, terminated, truncated, _ = env.step(action)
            if terminated or truncated:
                env.reset()
    rows = []
    for rank in range(2):
        handed = bench_env.read_action_log(path, rank, 6)
        assert handed.shape == (steps, 6) and (handed[0] == handed[1]).all() and (handed[1] != handed[2]).any()
        rows.append(correct.ring_entries(RING_CFG, 9, rank, handed))
    return path, rows


def _batch(rows, starts, length=5):
    """``[T, B]``: row ``b`` is ``length`` consecutive entries of env ``starts[b][0]`` from entry ``starts[b][1]``."""
    cut = [rows[rank][at : at + length] for rank, at in starts]
    stack = lambda key: np.stack([np.stack([np.asarray(e[key], np.float32).reshape(-1) for e in row]) for row in cut], 1)  # noqa: E731
    batch = {"rewards": stack("reward"), "terminated": stack("terminated"), "truncated": stack("truncated"),
             "is_first": stack("is_first"), "actions": stack("action")}  # fmt: skip
    batch["rgb"] = np.stack([np.stack([e["rgb"] for e in row]) for row in cut], 1)
    return batch


def test_the_entries_of_an_episode_end_as_the_loop_writes_them(tmp_path):
    _, rows = _ring(tmp_path)
    # 6-frame episodes under action repeat 2: three entries, the last frame with a zero action, then the reset's frame
    kinds = [(e["is_first"], e["truncated"], bool(np.any(e["action"]))) for e in rows[0][:9]]
    assert kinds[:5] == [(1.0, 0.0, True), (0.0, 0.0, True), (0.0, 0.0, True), (0.0, 1.0, False), (1.0, 0.0, True)]
    assert rows[0][3]["reward"] in (0.0, 1.0, 2.0) and rows[0][4]["reward"] == 0.0


def _altered(batch, fault):
    batch = {k: v.copy() for k, v in batch.items()}
    if fault == "rewards_of_another_row":
        batch["rewards"] = 1.0 - np.roll(batch["rewards"], 1, axis=1) * 0.5
    elif fault == "a_frame_altered":
        batch["rgb"][2, 1, 0, 0, 0] ^= 1
    elif fault == "rows_not_consecutive":
        batch = {k: np.concatenate([v[:2], v[3:]]) for k, v in batch.items()}
    elif fault == "an_action_altered":
        batch["actions"][1, 0, 3] += 0.25
    elif fault == "frames_of_another_env":
        batch["rgb"][:, 0] = batch["rgb"][:, 1]
    return batch


@pytest.mark.parametrize("fault", [None, "rewards_of_another_row", "a_frame_altered", "rows_not_consecutive", "an_action_altered",
                                   "frames_of_another_env"])  # fmt: skip
def test_ring_rows_counts_what_the_environments_did_not_produce(tmp_path, fault):
    path, rows = _ring(tmp_path)
    batches = [_altered(_batch(rows, [(0, 1), (1, 4), (0, 6)]), fault), _batch(rows, [(1, 0), (0, 2), (1, 7)])]
    seen = correct.ring_rows(RING_CFG, 9, batches, path)
    assert seen["positions"] == (27 if fault == "rows_not_consecutive" else 30) and seen["entries"] == [len(rows[0]), len(rows[1])]
    assert (seen["bad"] == 0) == (fault is None), seen
    assert len(seen["named"]) == min(seen["bad"], 3)


def test_player_gaps_read_a_latent_and_an_action_that_the_reference_would_not_sample():
    rng = np.random.default_rng(1)
    noisy_z, noisy = rng.normal(size=(3, 2, 4, 5)), rng.normal(size=(3, 2, 7))
    ref = {"h": np.zeros((3, 2, 8)), "noisy_z": noisy_z, "noisy": noisy, "action": None}
    z = np.eye(5)[noisy_z.argmax(-1)]
    action = np.eye(7)[noisy.argmax(-1)]
    same = correct.player_gaps({"h": np.zeros((3, 2, 8)), "z": z.reshape(3, 2, 20), "action": action}, ref)
    assert same["player_z"] == 0.0 and same["player_action"] == 0.0 and same["player_h"] == 0.0
    z[1, 0, 2] = np.eye(5)[noisy_z[1, 0, 2].argmin()]
    action[2, 1] = np.eye(7)[noisy[2, 1].argmin()]
    other = correct.player_gaps({"h": np.full((3, 2, 8), 0.5), "z": z.reshape(3, 2, 20), "action": action}, ref)
    assert other["player_z"] == pytest.approx(np.ptp(noisy_z[1, 0, 2])) and other["player_action"] == pytest.approx(np.ptp(noisy[2, 1]))
    assert other["player_h"] == 0.5
    # continuous actions: the largest difference of a component
    ref = {**ref, "noisy": None, "action": np.zeros((3, 2, 6))}
    assert correct.player_gaps({"h": ref["h"], "z": z.reshape(3, 2, 20), "action": np.full((3, 2, 6), 0.125)}, ref)["player_action"] == 0.125
