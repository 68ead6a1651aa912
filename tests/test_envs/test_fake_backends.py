"""Fake-backend tests for the four adapters whose binaries are absent from
CI: DMC, DIAMBRA, Super Mario Bros and MineRL.

Same technique as the Crafter test (test_env_adapters.py): stub the minimal
external API surface in sys.modules, import the adapter fresh, and drive its
obs/action remap end to end — spec→Box conversion and action rescaling for
DMC, sub-space normalization for DIAMBRA, the joypad menu and clock-based
termination split for Mario, and the action menu / camera clamp / multi-hot
inventory encoding for MineRL (incl. the navigate/obtain custom specs).
"""

import importlib
import sys
import types

import numpy as np
import pytest

# --------------------------------------------------------------------- DMC


def _install_fake_dmc(monkeypatch):
    dm_env = types.ModuleType("dm_env")
    specs_mod = types.ModuleType("dm_env.specs")

    class Array:
        def __init__(self, shape, dtype=np.float64, name=None):
            self.shape = tuple(shape)
            self.dtype = dtype
            self.name = name

    class BoundedArray(Array):
        def __init__(self, shape, dtype=np.float64, minimum=-1.0, maximum=1.0, name=None):
            super().__init__(shape, dtype, name)
            self.minimum = np.asarray(minimum)
            self.maximum = np.asarray(maximum)

    specs_mod.Array = Array
    specs_mod.BoundedArray = BoundedArray
    dm_env.specs = specs_mod

    class TimeStep:
        def __init__(self, observation, reward, discount, last):
            self.observation = observation
            self.reward = reward
            self.discount = discount
            self._last = last

        def last(self):
            return self._last

    class FakePhysics:
        def get_state(self):
            return np.arange(3, dtype=np.float64)

        def render(self, height, width, camera_id=0):
            return np.full((height, width, 3), 7, np.uint8)

    class FakeTask:
        _random = None

    class FakeDmcEnv:
        def __init__(self):
            self.physics = FakePhysics()
            self.task = FakeTask()
            self.received_actions = []
            self._steps = 0

        def action_spec(self):
            # true bounds [0, 10] x2: exercises the [-1, 1] rescale
            return BoundedArray((2,), np.float64, minimum=0.0, maximum=10.0)

        def reward_spec(self):
            return BoundedArray((), np.float64, minimum=0.0, maximum=1.0)

        def observation_spec(self):
            return {
                "position": BoundedArray((2,), np.float64, minimum=-5.0, maximum=5.0),
                "velocity": Array((3,), np.float64),
            }

        def reset(self):
            self._steps = 0
            return TimeStep({"position": np.zeros(2), "velocity": np.ones(3)}, None, 1.0, False)

        def step(self, action):
            self.received_actions.append(np.asarray(action))
            self._steps += 1
            # 3rd step ends by time limit (discount 1), 5th by termination
            last = self._steps in (3, 5)
            discount = 0.0 if self._steps == 5 else 1.0
            obs = {"position": np.full(2, self._steps, np.float64), "velocity": np.ones(3)}
            return TimeStep(obs, 0.5, discount, last)

        def close(self):
            pass

    suite_mod = types.ModuleType("dm_control.suite")
    fake_env_holder = {}

    def load(domain_name, task_name, task_kwargs=None, visualize_reward=False, environment_kwargs=None):
        env = FakeDmcEnv()
        fake_env_holder["env"] = env
        return env

    suite_mod.load = load
    dm_control = types.ModuleType("dm_control")
    dm_control.suite = suite_mod
    monkeypatch.setitem(sys.modules, "dm_env", dm_env)
    monkeypatch.setitem(sys.modules, "dm_env.specs", specs_mod)
    monkeypatch.setitem(sys.modules, "dm_control", dm_control)
    monkeypatch.setitem(sys.modules, "dm_control.suite", suite_mod)
    monkeypatch.setattr("sheeprl_tpu.utils.imports._IS_DMC_AVAILABLE", True)
    sys.modules.pop("sheeprl_tpu.envs.dmc", None)
    return fake_env_holder


def test_dmc_adapter_with_fake_backend(monkeypatch):
    holder = _install_fake_dmc(monkeypatch)
    dmc_mod = importlib.import_module("sheeprl_tpu.envs.dmc")

    env = dmc_mod.DMCWrapper("walker", "walk", from_pixels=True, from_vectors=True, height=16, width=16, seed=3)
    # spec -> Box: bounded position [-5, 5] concat unbounded velocity
    state_space = env.observation_space["state"]
    assert state_space.shape == (5,)
    assert np.allclose(state_space.low[:2], -5) and np.isneginf(state_space.low[2:]).all()
    assert env.action_space.shape == (2,) and np.allclose(env.action_space.low, -1)

    obs, _ = env.reset(seed=11)
    assert holder["env"].task._random is not None  # seeding reached the task
    assert obs["rgb"].shape == (16, 16, 3) and obs["rgb"].dtype == np.uint8
    assert obs["state"].shape == (5,)

    # [-1, 1] -> [0, 10] rescale: -1 -> 0, 0 -> 5, +1 -> 10
    env.step(np.array([-1.0, 1.0], np.float32))
    assert np.allclose(holder["env"].received_actions[-1], [0.0, 10.0])
    env.step(np.array([0.0, 0.0], np.float32))
    assert np.allclose(holder["env"].received_actions[-1], [5.0, 5.0])

    # discount-based split: step 3 is a time limit, step 5 a termination
    _, _, terminated, truncated, info = env.step(np.zeros(2, np.float32))
    assert truncated and not terminated and info["discount"] == 1.0
    env.step(np.zeros(2, np.float32))
    _, _, terminated, truncated, info = env.step(np.zeros(2, np.float32))
    assert terminated and not truncated and info["discount"] == 0.0
    assert info["internal_state"].shape == (3,)
    sys.modules.pop("sheeprl_tpu.envs.dmc", None)


def test_dmc_adapter_rejects_no_obs_source(monkeypatch):
    _install_fake_dmc(monkeypatch)
    dmc_mod = importlib.import_module("sheeprl_tpu.envs.dmc")
    with pytest.raises(ValueError, match="must not be both False"):
        dmc_mod.DMCWrapper("walker", "walk", from_pixels=False, from_vectors=False)
    sys.modules.pop("sheeprl_tpu.envs.dmc", None)


def test_dmc_variant_wrappers_with_fake_backend(monkeypatch):
    """The fork-experiment variants layer distractor observations on the base
    adapter (reference dmc_64.py:153-201 / dmc_extended.py): every declared
    space must be produced at reset AND step, with the combined scalar mixing
    pixel[0,0,0] with state[0]."""
    _install_fake_dmc(monkeypatch)
    sys.modules.pop("sheeprl_tpu.envs.dmc_variants", None)
    variants = importlib.import_module("sheeprl_tpu.envs.dmc_variants")

    env = variants.DMC64Wrapper("walker", "walk", from_pixels=True, from_vectors=True, height=16, width=16)
    assert env.observation_space["camera_rgb"].shape == (64, 64, 1)
    assert env.observation_space["camera_depth"].shape == (64, 64, 1)
    for obs in (env.reset()[0], env.step(np.zeros(2, np.float32))[0]):
        assert set(obs) == set(env.observation_space.spaces)
        for k, space in env.observation_space.spaces.items():
            assert obs[k].shape == space.shape, k

    env = variants.DMCExtendedWrapper("walker", "walk", from_pixels=True, from_vectors=True, height=16, width=16)
    assert env.observation_space["random_img"].shape == (16, 16, 3)
    assert env.observation_space["random_values"].shape == (10,)
    obs, _ = env.reset()
    assert set(obs) == set(env.observation_space.spaces)
    assert np.isclose(obs["combined_values"][0], float(obs["rgb"][0, 0, 0]) + float(obs["state"][0]))

    # vectors-only: no distractors beyond the base spaces
    env = variants.DMCExtendedWrapper("walker", "walk", from_pixels=False, from_vectors=True)
    assert set(env.observation_space.spaces) == {"state"}
    sys.modules.pop("sheeprl_tpu.envs.dmc_variants", None)
    sys.modules.pop("sheeprl_tpu.envs.dmc", None)


def test_dmc_through_env_factory(monkeypatch):
    """Drive the full factory path (``env=dmc`` config -> make_env thunk ->
    wrapped Dict obs env) against the fake backend — the adapter contract the
    reference exercises with real dm_control (sheeprl/envs/dmc.py:49-244)."""
    _install_fake_dmc(monkeypatch)
    sys.modules.pop("sheeprl_tpu.envs.dmc", None)

    from sheeprl_tpu.config import compose
    from sheeprl_tpu.envs.factory import make_env
    from sheeprl_tpu.utils.utils import dotdict

    cfg = dotdict(
        compose(
            "config",
            [
                "exp=dreamer_v3",
                "env=dmc",
                "env.capture_video=False",
                "env.screen_size=16",
                "algo.cnn_keys.encoder=[rgb]",
                "algo.mlp_keys.encoder=[]",
                "metric.log_level=0",
            ],
        )
    )
    env = make_env(cfg, seed=7, rank=0)()
    try:
        obs, _ = env.reset(seed=7)
        assert obs["rgb"].shape == (16, 16, 3) and obs["rgb"].dtype == np.uint8
        # action_repeat=2 (the dmc recipe): one env.step drives two backend steps
        obs, reward, terminated, truncated, info = env.step(env.action_space.sample())
        assert obs["rgb"].shape == (16, 16, 3)
        assert np.isclose(reward, 1.0)  # 2 backend steps x 0.5 reward each
    finally:
        env.close()
    sys.modules.pop("sheeprl_tpu.envs.dmc", None)


# ------------------------------------------------------------------ DIAMBRA


def _install_fake_diambra(monkeypatch):
    import gymnasium as gym

    class Settings(dict):
        """diambra settings object: kwargs-dict with attribute access."""

        def __init__(self, **kwargs):
            super().__init__(**kwargs)

        def __setattr__(self, k, v):
            self[k] = v

        def __getattr__(self, k):
            try:
                return self[k]
            except KeyError:
                raise AttributeError(k)

    class FakeEngine(gym.Env):
        def __init__(self, settings, wrappers):
            self.settings = settings
            self.wrappers = wrappers
            self.observation_space = gym.spaces.Dict(
                {
                    "frame": gym.spaces.Box(0, 255, (64, 64, 1), np.uint8),
                    "stage": gym.spaces.Discrete(4),
                    "moves": gym.spaces.MultiDiscrete([3, 5]),
                }
            )
            self.action_space = gym.spaces.Discrete(6)
            self._steps = 0

        def reset(self, seed=None, options=None):
            self._steps = 0
            return self._obs(), {}

        def _obs(self):
            return {
                "frame": np.zeros((64, 64, 1), np.uint8),
                "stage": 2,  # scalar: the adapter must reshape to (1,)
                "moves": np.array([1, 4]),
            }

        def step(self, action):
            self._steps += 1
            info = {"env_done": self._steps >= 3}
            return self._obs(), 1.0, False, False, info

        def close(self):
            pass

    made = {}

    def make(game_id, settings, wrappers, rank=0, render_mode="rgb_array", log_level=0):
        engine = FakeEngine(settings, wrappers)
        made["engine"] = engine
        return engine

    class SpaceTypes:
        DISCRETE = "discrete"
        MULTI_DISCRETE = "multi_discrete"

    class Roles:
        P1 = "p1"
        P2 = "p2"

    arena = types.ModuleType("diambra.arena")
    arena.make = make
    arena.EnvironmentSettings = Settings
    arena.WrappersSettings = Settings
    arena.SpaceTypes = SpaceTypes
    arena.Roles = Roles
    diambra = types.ModuleType("diambra")
    diambra.arena = arena
    monkeypatch.setitem(sys.modules, "diambra", diambra)
    monkeypatch.setitem(sys.modules, "diambra.arena", arena)
    monkeypatch.setattr("sheeprl_tpu.utils.imports._IS_DIAMBRA_AVAILABLE", True)
    sys.modules.pop("sheeprl_tpu.envs.diambra", None)
    return made


def test_diambra_adapter_with_fake_backend(monkeypatch):
    import gymnasium as gym

    made = _install_fake_diambra(monkeypatch)
    diambra_mod = importlib.import_module("sheeprl_tpu.envs.diambra")

    with pytest.warns(UserWarning, match="managed by the wrapper"):
        env = diambra_mod.DiambraWrapper(
            "doapp",
            repeat_action=2,
            diambra_settings={"frame_shape": (128, 128, 0), "difficulty": 3},
            diambra_wrappers={"stack_frames": 4},
        )
    # managed keys stripped, user keys kept, step_ratio forced under repeat
    assert made["engine"].settings["difficulty"] == 3
    assert made["engine"].settings["step_ratio"] == 1
    assert made["engine"].wrappers["flatten"] is True
    # engine-side resize (increase_performance default)
    assert made["engine"].settings["frame_shape"] == (64, 64, 0)

    # Discrete/MultiDiscrete sub-spaces re-expressed as int32 Boxes
    assert isinstance(env.observation_space["stage"], gym.spaces.Box)
    assert env.observation_space["stage"].dtype == np.int32
    assert env.observation_space["moves"].shape == (2,)

    obs, info = env.reset()
    assert info["env_domain"] == "DIAMBRA"
    assert obs["stage"].shape == (1,) and obs["moves"].shape == (2,)

    # numpy discrete action unwraps to a python int; env_done -> terminated
    env.step(np.array([2]))
    env.step(np.array(1))
    _, _, terminated, truncated, info = env.step(3)
    assert terminated and not truncated
    with pytest.raises(ValueError, match="action_space must be"):
        diambra_mod.DiambraWrapper("doapp", action_space="BOGUS")
    sys.modules.pop("sheeprl_tpu.envs.diambra", None)


# ------------------------------------------------------------------- Mario


def _install_fake_mario(monkeypatch):
    class FakeNes:
        """old-gym NES env: 4-tuple step, bare reset, info['time'] clock."""

        class observation_space:
            low = np.zeros((240, 256, 3), np.uint8)
            high = np.full((240, 256, 3), 255, np.uint8)
            shape = (240, 256, 3)
            dtype = np.dtype(np.uint8)

        def __init__(self):
            self._steps = 0
            self.reset_seeds = []

        def reset(self, seed=None, options=None):
            self.reset_seeds.append(seed)
            self._steps = 0
            return np.zeros((240, 256, 3), np.uint8)

        def step(self, action):
            assert isinstance(action, int)
            self._steps += 1
            done = self._steps >= 2
            # first episode ends with clock running (truncated), info set below
            return np.zeros((240, 256, 3), np.uint8), 1.0, done, {"time": self.clock}

        def render(self, mode="rgb_array"):
            return np.zeros((240, 256, 3), np.uint8)

        clock = 250

    class FakeJoypad:
        def __init__(self, env, menu):
            self.env = env
            self.menu = menu
            self.observation_space = env.observation_space

        def step(self, action):
            return self.env.step(action)

        def reset(self):
            return self.env.reset()

        def render(self, mode="rgb_array"):
            return self.env.render(mode)

    gsm = types.ModuleType("gym_super_mario_bros")
    gsm.make = lambda id: FakeNes()
    actions = types.ModuleType("gym_super_mario_bros.actions")
    actions.RIGHT_ONLY = [["NOOP"], ["right"]]
    actions.SIMPLE_MOVEMENT = [["NOOP"], ["right"], ["right", "A"]]
    actions.COMPLEX_MOVEMENT = [["NOOP"]] * 12
    gsm.actions = actions
    nes_py = types.ModuleType("nes_py")
    wrappers = types.ModuleType("nes_py.wrappers")
    wrappers.JoypadSpace = FakeJoypad
    nes_py.wrappers = wrappers
    monkeypatch.setitem(sys.modules, "gym_super_mario_bros", gsm)
    monkeypatch.setitem(sys.modules, "gym_super_mario_bros.actions", actions)
    monkeypatch.setitem(sys.modules, "nes_py", nes_py)
    monkeypatch.setitem(sys.modules, "nes_py.wrappers", wrappers)
    monkeypatch.setattr("sheeprl_tpu.utils.imports._IS_SUPER_MARIO_AVAILABLE", True)
    sys.modules.pop("sheeprl_tpu.envs.super_mario_bros", None)
    return FakeNes


def test_mario_adapter_with_fake_backend(monkeypatch):
    FakeNes = _install_fake_mario(monkeypatch)
    mario_mod = importlib.import_module("sheeprl_tpu.envs.super_mario_bros")

    env = mario_mod.SuperMarioBrosWrapper("SuperMarioBros-v0", action_space="simple")
    assert env.action_space.n == 3  # SIMPLE_MOVEMENT menu length
    obs, _ = env.reset(seed=5)
    assert env.raw.env.reset_seeds == [5]  # seed bypasses JoypadSpace
    assert set(obs) == {"rgb"} and obs["rgb"].shape == (240, 256, 3)

    # clock running at episode end => truncated (timeout death is a cutoff)
    env.step(np.array([1]))
    _, _, terminated, truncated, _ = env.step(np.array(1))
    assert truncated and not terminated

    # clock at zero => real termination
    FakeNes.clock = 0
    env.reset()
    env.step(np.array(0))
    _, _, terminated, truncated, _ = env.step(np.array(0))
    assert terminated and not truncated
    FakeNes.clock = 250
    sys.modules.pop("sheeprl_tpu.envs.super_mario_bros", None)


# ------------------------------------------------------------------ MineRL


ALL_ITEMS = ["air", "compass", "dirt", "log", "planks", "stick", "diamond", "iron_pickaxe"]
KEYMAP = {
    "forward": 17, "back": 31, "left": 30, "right": 32,
    "jump": 57, "sneak": 42, "sprint": 29, "attack": -100, "use": -99,
}


def _install_fake_minerl(monkeypatch):
    class Handler:
        pass

    class Enum:
        def __init__(self, values):
            self.values = np.asarray(list(values))

    class _Recorder(Handler):
        def __init__(self, *args, **kwargs):
            self.args = args
            self.kwargs = kwargs

    class KeybasedCommandAction(_Recorder):
        def __init__(self, key, keycode):
            super().__init__(key, keycode)
            self.key = key

    class CameraAction(_Recorder):
        key = "camera"

    def enum_handler(key_name):
        class H(_Recorder):
            key = key_name

            def __init__(self, values, *a, **k):
                super().__init__(values, *a, **k)
                self.values = list(values)

        H.__name__ = f"Enum_{key_name}"
        return H

    PlaceBlock = enum_handler("place")
    EquipAction = enum_handler("equip")
    CraftAction = enum_handler("craft")
    CraftNearbyAction = enum_handler("nearbyCraft")
    SmeltItemNearby = enum_handler("nearbySmelt")

    class FlatInventoryObservation(_Recorder):
        def __init__(self, items):
            super().__init__(items)
            self.items = list(items)

    class EquippedItemObservation(_Recorder):
        def __init__(self, items, _default="air", _other="other"):
            super().__init__(items)
            self.items = list(items)

    class CompassObservation(_Recorder):
        pass

    class POVObservation(_Recorder):
        pass

    plain = (
        "ObservationFromCurrentLocation", "ObservationFromLifeStats",
        "TimeInitialCondition", "WeatherInitialCondition", "SpawningInitialCondition",
        "ServerQuitWhenAnyAgentFinishes", "DefaultWorldGenerator",
        "SimpleInventoryAgentStart", "AgentQuitFromTouchingBlockType",
        "RewardForTouchingBlockType", "RewardForDistanceTraveledToCompassTarget",
        "BiomeGenerator", "NavigationDecorator", "RewardForCollectingItemsOnce",
        "RewardForCollectingItems", "AgentQuitFromPossessingItem",
        "AgentQuitFromCraftingItem",
    )

    class FakeDictSpace:
        def __init__(self, entries):
            self.spaces = dict(entries)

        def __iter__(self):
            return iter(self.spaces)

        def __getitem__(self, k):
            return self.spaces[k]

    class FakeRawMineRL:
        """Raw env assembled from the spec's handler tables — the adapter's
        menu/obs construction sees exactly what the spec declared."""

        def __init__(self, spec):
            self.spec = spec
            self.commands = []
            act = {}
            for h in spec.create_actionables():
                if isinstance(h, KeybasedCommandAction):
                    act[h.key] = object()
                elif isinstance(h, CameraAction):
                    act["camera"] = object()
                else:
                    act[h.key] = Enum(h.values)
            self.action_space = FakeDictSpace(act)

            obs = {"pov": object(), "life_stats": object()}
            for h in spec.create_observables():
                if isinstance(h, FlatInventoryObservation):
                    obs["inventory"] = FakeDictSpace({i: object() for i in h.items})
                elif isinstance(h, EquippedItemObservation):
                    obs["equipped_items"] = FakeDictSpace(
                        {"mainhand": FakeDictSpace({"type": Enum(h.items)})}
                    )
                elif isinstance(h, CompassObservation):
                    obs["compass"] = object()
            self.observation_space = FakeDictSpace(obs)

        def _obs(self):
            # inventory keyed by the task's declared FlatInventoryObservation
            # items (what the real backend reports)
            inv_items = (
                list(self.observation_space["inventory"].spaces)
                if "inventory" in self.observation_space.spaces
                else []
            )
            raw = {
                "pov": np.full((64, 64, 3), 9, np.uint8),
                "life_stats": {"life": 20.0, "food": 18.0, "air": 300.0},
                "inventory": {i: (3 if i == "dirt" else 0) for i in inv_items},
            }
            if "compass" in self.observation_space.spaces:
                raw["compass"] = {"angle": np.array([42.0])}
            if "equipped_items" in self.observation_space.spaces:
                raw["equipped_items"] = {"mainhand": {"type": "air"}}
            return raw

        def reset(self):
            return self._obs()

        def step(self, command):
            self.commands.append(command)
            return self._obs(), 1.0, False, {}

    class EnvSpec:
        def __init__(self, name=None, *args, max_episode_steps=None, **kwargs):
            self.name = name
            self.max_episode_steps = max_episode_steps

        def make(self):
            return FakeRawMineRL(self)

    minerl = types.ModuleType("minerl")
    herobraine = types.ModuleType("minerl.herobraine")
    hero = types.ModuleType("minerl.herobraine.hero")
    mc = types.ModuleType("minerl.herobraine.hero.mc")
    mc.ALL_ITEMS = list(ALL_ITEMS)
    mc.INVERSE_KEYMAP = dict(KEYMAP)
    spaces_mod = types.ModuleType("minerl.herobraine.hero.spaces")
    spaces_mod.Enum = Enum
    handler_mod = types.ModuleType("minerl.herobraine.hero.handler")
    handler_mod.Handler = Handler
    handlers_mod = types.ModuleType("minerl.herobraine.hero.handlers")
    handlers_mod.KeybasedCommandAction = KeybasedCommandAction
    handlers_mod.CameraAction = CameraAction
    handlers_mod.PlaceBlock = PlaceBlock
    handlers_mod.EquipAction = EquipAction
    handlers_mod.CraftAction = CraftAction
    handlers_mod.CraftNearbyAction = CraftNearbyAction
    handlers_mod.SmeltItemNearby = SmeltItemNearby
    handlers_mod.FlatInventoryObservation = FlatInventoryObservation
    handlers_mod.EquippedItemObservation = EquippedItemObservation
    handlers_mod.CompassObservation = CompassObservation
    handlers_mod.POVObservation = POVObservation
    for name in plain:
        setattr(handlers_mod, name, type(name, (_Recorder,), {}))
    env_spec_mod = types.ModuleType("minerl.herobraine.env_spec")
    env_spec_mod.EnvSpec = EnvSpec

    hero.mc = mc
    hero.spaces = spaces_mod
    hero.handler = handler_mod
    hero.handlers = handlers_mod
    herobraine.hero = hero
    herobraine.env_spec = env_spec_mod
    minerl.herobraine = herobraine
    for mod_name, mod in [
        ("minerl", minerl),
        ("minerl.herobraine", herobraine),
        ("minerl.herobraine.hero", hero),
        ("minerl.herobraine.hero.mc", mc),
        ("minerl.herobraine.hero.spaces", spaces_mod),
        ("minerl.herobraine.hero.handler", handler_mod),
        ("minerl.herobraine.hero.handlers", handlers_mod),
        ("minerl.herobraine.env_spec", env_spec_mod),
    ]:
        monkeypatch.setitem(sys.modules, mod_name, mod)
    monkeypatch.setattr("sheeprl_tpu.utils.imports._IS_MINERL_AVAILABLE", True)
    for mod in [
        "sheeprl_tpu.envs.minerl",
        "sheeprl_tpu.envs.minerl_envs.backend",
        "sheeprl_tpu.envs.minerl_envs.navigate",
        "sheeprl_tpu.envs.minerl_envs.obtain",
    ]:
        sys.modules.pop(mod, None)


def _cleanup_minerl_modules():
    for mod in [
        "sheeprl_tpu.envs.minerl",
        "sheeprl_tpu.envs.minerl_envs.backend",
        "sheeprl_tpu.envs.minerl_envs.navigate",
        "sheeprl_tpu.envs.minerl_envs.obtain",
    ]:
        sys.modules.pop(mod, None)


def test_minerl_navigate_adapter_with_fake_backend(monkeypatch):
    _install_fake_minerl(monkeypatch)
    minerl_mod = importlib.import_module("sheeprl_tpu.envs.minerl")

    env = minerl_mod.MineRLWrapper(
        "custom_navigate", dense=True, extreme=False, seed=7, multihot_inventory=True
    )
    menu = env.action_menu
    assert menu[0] == {}  # no-op entry
    # 8 keyboard keys + 4 camera moves + "dirt" place + no-op
    assert len(menu) == 1 + 8 + 4 + 1
    # jump/sneak/sprint imply forward
    jump_entries = [e for e in menu.values() if e.get("jump") == 1]
    assert jump_entries and all(e["forward"] == 1 for e in jump_entries)
    # enum entry for place=dirt exists ("none" excluded)
    assert {"place": "dirt"} in menu.values()
    # camera entries are the four fixed moves
    cameras = [e["camera"] for e in menu.values() if "camera" in e]
    assert len(cameras) == 4

    obs, _ = env.reset(seed=7)
    assert obs["rgb"].shape == (64, 64, 3)
    assert obs["compass"].shape == (1,) and obs["compass"][0] == 42.0
    # multi-hot inventory against the global item table
    assert obs["inventory"].shape == (len(ALL_ITEMS),)
    assert obs["inventory"][ALL_ITEMS.index("dirt")] == 3
    assert np.array_equal(obs["max_inventory"], obs["inventory"])
    assert obs["life_stats"].tolist() == [20.0, 18.0, 300.0]
    # the air-counts-as-1 rule (air stacks are unbounded in the raw counts)
    packed = env._pack_observation(
        {
            "pov": np.zeros((64, 64, 3), np.uint8),
            "life_stats": {"life": 20.0, "food": 20.0, "air": 300.0},
            "inventory": {"air": 64, "dirt": 2},
            "compass": {"angle": np.array([0.0])},
        }
    )
    assert packed["inventory"][ALL_ITEMS.index("air")] == 1
    # max_inventory is monotonic: dirt high-water mark stays 3
    assert packed["max_inventory"][ALL_ITEMS.index("dirt")] == 3

    # action translation: camera pitch clamp at the limits
    pitch_down = next(
        i for i, e in enumerate(menu.values()) if "camera" in e and e["camera"][0] < 0
    )
    for _ in range(5):
        env.step(np.array(pitch_down))  # -15 x 5 = -75 < limit -60
    sent = env.raw.commands
    # the 5th pitch move would cross -60: camera zeroed on the pitch axis
    assert sent[4]["camera"][0] == 0
    assert sum(c["camera"][0] for c in sent) == -60.0
    _cleanup_minerl_modules()


def test_minerl_obtain_adapter_non_multihot(monkeypatch):
    _install_fake_minerl(monkeypatch)
    minerl_mod = importlib.import_module("sheeprl_tpu.envs.minerl")

    env = minerl_mod.MineRLWrapper("custom_obtain_diamond", dense=False, multihot_inventory=False)
    # task-local inventory indexing: 18 tracked items
    assert env.observation_space["inventory"].shape == (18,)
    # equipment one-hot over the task's equip enum (air + 6 tools + other)
    assert env.observation_space["equipment"].shape == (8,)
    obs, _ = env.reset()
    assert obs["equipment"].sum() == 1  # exactly one held item
    assert "compass" not in obs  # obtain tasks have no compass

    # enum menu entries route to the right command key
    craft_entries = [e for e in env.action_menu.values() if "nearbyCraft" in e]
    assert craft_entries and all(v != "none" for e in craft_entries for v in e.values())
    env.step(np.array(0))
    assert env.raw.commands[-1]["craft"] == "none"  # no-op keeps full NOOP dict
    _cleanup_minerl_modules()


def test_minerl_custom_spec_tables(monkeypatch):
    _install_fake_minerl(monkeypatch)
    navigate = importlib.import_module("sheeprl_tpu.envs.minerl_envs.navigate")
    obtain = importlib.import_module("sheeprl_tpu.envs.minerl_envs.obtain")

    nav = navigate.CustomNavigate(dense=True, extreme=True, break_speed=100)
    assert nav.name == "CustomMineRLNavigateExtremeDense-v0"
    assert nav.is_from_folder("navigateextreme")
    # dense variant adds the distance-shaping reward
    rewardables = nav.create_rewardables()
    assert len(rewardables) == 2
    # extreme variant generates the mountain biome
    gens = nav.create_server_world_generators()
    assert type(gens[0]).__name__ == "BiomeGenerator"
    assert nav.determine_success_from_rewards([100.0, 60.0])
    assert not nav.determine_success_from_rewards([100.0])

    dia = obtain.CustomObtainDiamond(dense=False)
    ladder = dia.reward_schedule
    assert ladder[-1] == {"type": "diamond", "amount": 1, "reward": 1024}
    assert type(dia.create_rewardables()[0]).__name__ == "RewardForCollectingItemsOnce"
    dense_dia = obtain.CustomObtainDiamond(dense=True)
    assert type(dense_dia.create_rewardables()[0]).__name__ == "RewardForCollectingItems"

    pick = obtain.CustomObtainIronPickaxe(dense=False)
    assert type(pick.create_agent_handlers()[0]).__name__ == "AgentQuitFromCraftingItem"
    # success = hitting every DISTINCT rung within 10% slack (reference
    # obtain.py:160-168 parity, including its set-vs-duplicates quirk: the
    # stock ladders repeat values 4 and 32, so they can never fully "hit")
    custom = obtain.CustomObtain(
        target_item="log",
        dense=False,
        reward_schedule=[
            dict(type="log", amount=1, reward=1),
            dict(type="planks", amount=1, reward=2),
            dict(type="stick", amount=1, reward=4),
        ],
    )
    assert custom.determine_success_from_rewards([1, 2, 4])
    assert not custom.determine_success_from_rewards([1, 2])
    _cleanup_minerl_modules()
