"""RL: PPO on CartPole with distributed env runners.

Reference-Ray equivalent: ``doc/source/rllib/getting-started`` (new API
stack: EnvRunners + RLModule + Learner).
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Env runners + learner are host processes sharing this machine: pin JAX
# to CPU (on a TPU cluster the GSPMD MeshLearner owns the chips instead).
# The head and every worker inherit the pin.
os.environ.setdefault("RAY_TPU_JAX_PLATFORM", "cpu")

import ray_tpu
from ray_tpu.rl import PPOConfig


def main():
    ray_tpu.init(num_cpus=4, probe_tpu=False)
    config = (PPOConfig()
              .environment("CartPole-v1")
              .env_runners(num_env_runners=2, rollout_fragment_length=256)
              .training(lr=3e-3, minibatch_size=128, num_epochs=6,
                        gamma=0.99))
    algo = config.build()
    for i in range(5):
        result = algo.train()
        print(f"iter {i}: return_mean="
              f"{result['episode_return_mean']:.1f} "
              f"steps={result.get('num_env_steps_sampled', '?')}")
    algo.stop()
    ray_tpu.shutdown()


if __name__ == "__main__":
    main()
