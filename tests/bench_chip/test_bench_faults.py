"""A whole run with the timed path broken underneath comes out not correct:
for each fault a cell can have. The harness's look for a chip is skipped;
everything else runs as on the chip, at a tiny size, under the cell's own
limits."""
import bench_chip_helpers as helpers
from repro.models import transformer as tf
from repro.runtime import serve

SEED = 2**32 + 9


def test_sound_serving_run_is_correct():
    res = helpers.run_tiny("tiny-qwen3", seed=SEED, seconds=3.0)
    assert res["correct"], res["checks"]
    assert res["summary"]["compared_tokens"] > 0


def test_serving_step_that_returns_its_state_unchanged(monkeypatch):
    real = tf.decode_step

    def stale(cfg, params, caches, batch, pos):
        logits, _ = real(cfg, params, caches, batch, pos)
        return logits, caches
    monkeypatch.setattr(tf, "decode_step", stale)
    res = helpers.run_tiny("tiny-qwen3", seed=SEED, seconds=3.0)
    assert not res["correct"], res["checks"]


def test_serving_token_altered_where_produced(monkeypatch):
    real = serve.ServingEngine.step
    vocab = 512

    def step(self):
        real(self)
        for req in self.slots + self.finished[-len(self.slots):]:
            if req is not None and len(req.generated) == 3:
                req.generated[-1] = (req.generated[-1] + 1) % vocab
    monkeypatch.setattr(serve.ServingEngine, "step", step)
    res = helpers.run_tiny("tiny-qwen3", seed=SEED, seconds=3.0)
    assert not res["correct"], res["checks"]

