"""Faults planted under a benchmark run, each of which the check must
catch: the timed path is broken underneath the harness, at a size the
CPU runs."""
import dataclasses

import jax.numpy as jnp

from repro.core import schedule
from repro.runtime import live, transport, workload
from repro.runtime.stage_executor import StageExecutor


def unchanged_state(mp):
    """Every step returns the weights it was given."""
    orig = StageExecutor.step

    def step(self, fwd_buf, new_buf, mom_buf, x, ct=None, batch=None):
        dx, _, mom = orig(self, fwd_buf, new_buf, mom_buf, x, ct, batch)
        return dx, new_buf, mom
    mp.setattr(StageExecutor, "step", step)


def half_batch(mp):
    """Half of every batch is left out; the mean is taken over the rest."""
    orig = workload.mobilenet_chain

    def chain(*a, **kw):
        c = orig(*a, **kw)
        loss = c.loss

        def half_loss(y, batch):
            return loss(y, {"labels": batch["labels"][:y.shape[0]]})
        return dataclasses.replace(
            c, input_of=lambda b: b["x"][:b["x"].shape[0] // 2],
            loss=half_loss)
    mp.setattr(workload, "mobilenet_chain", chain)


def no_exchange(mp):
    """Activations and gradients never cross between stages: the
    receiver gets zeros."""
    orig = transport.Transport.send

    def send(self, src, dst, kind, payload=None, **kw):
        if kind in ("act", "grad"):
            seg, b, x = payload
            payload = (seg, b, jnp.zeros_like(x))
        return orig(self, src, dst, kind, payload, **kw)
    mp.setattr(transport.Transport, "send", send)


def altered_loss(mp):
    """The last stage reports each loss 10% off where it computes it."""
    orig = StageExecutor.forward

    def forward(self, buf, x, batch=None):
        out = orig(self, buf, x, batch)
        return out * 1.1 if self.last else out
    mp.setattr(StageExecutor, "forward", forward)


def latest_weights(mp):
    """Every batch runs on the newest weights, not its stashed version."""
    mp.setattr(schedule, "version_for_batch", lambda b, n: b)


def zeroed_handoff(mp):
    """At a recovery, every layer a survivor takes over arrives zeroed."""
    orig = live.Worker.install

    def install(self, layer_range, flats, version=0):
        if self.stash is not None:
            a, e = self.layer_range
            flats = {j: w if a <= j <= e else jnp.zeros_like(w)
                     for j, w in flats.items()}
        return orig(self, layer_range, flats, version)
    mp.setattr(live.Worker, "install", install)


FAULTS = {"unchanged_state": unchanged_state, "half_batch": half_batch,
          "no_exchange": no_exchange, "altered_loss": altered_loss,
          "latest_weights": latest_weights, "zeroed_handoff": zeroed_handoff}


def run_tiny(cell, seed=5, seconds=2.0, cache_dir=None):
    from benchmarks.chip import run
    return run.run_cell(cell, seed, seconds, False, require_chip=False,
                        cache_dir=cache_dir)
