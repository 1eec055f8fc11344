"""Crash-injection child for ``tests/test_resume.py`` (DESIGN.md §13).

Runs one controlled fleet/serve horizon with chunk-boundary checkpointing
and — when told to — kills ITSELF (SIGKILL/SIGTERM, optionally corrupting
the newest checkpoint first to simulate a torn mid-write kill) immediately
after the j-th checkpoint save.  Self-killing after a scripted save makes
the crash land deterministically at a chunk boundary; the parent
randomizes j.  A run that completes writes its full-horizon telemetry,
final charge, and packed controller history to ``--out`` (npz) so the
parent can compare kill-and-resume runs bit-exactly against uninterrupted
ones, and asserts the whole horizon compiled exactly one chunk program
(resume must add zero jit-cache entries).

The scenario is the exact-arithmetic config of the sharded-parity children
(zero leak, dyadic grid): every fp32 partial sum is exact, so host-local,
padded, 8-device sharded, lax and pallas runs must all agree bitwise.
"""
import argparse
import os
import signal
import sys

import numpy as np

from repro.checkpoint import RunCheckpointer, pack_controller

SIGNALS = {"KILL": signal.SIGKILL, "TERM": signal.SIGTERM}


class KillingCheckpointer(RunCheckpointer):
    """`RunCheckpointer` that self-kills after the ``kill_after``-th save,
    optionally tearing the just-written file first (a kill mid-write)."""

    def __init__(self, directory, *, kill_after=None, sig=signal.SIGKILL,
                 corrupt="none", keep=3):
        super().__init__(directory, keep=keep)
        self.kill_after, self.sig, self.corrupt = kill_after, sig, corrupt
        self.saves = 0

    def save(self, step, tree, metadata=None):
        path = super().save(step, tree, metadata)
        self.saves += 1
        if self.kill_after is not None and self.saves >= self.kill_after:
            if self.corrupt == "truncate":
                with open(path, "r+b") as f:
                    f.truncate(max(1, os.path.getsize(path) // 2))
            elif self.corrupt == "garbage":
                with open(path, "r+b") as f:
                    f.write(b"\x00" * 64)
            sys.stdout.flush()
            os.kill(os.getpid(), self.sig)
        return path


def make_mesh(want_mesh):
    if not want_mesh:
        return None
    import jax

    from repro.launch.mesh import make_data_mesh

    n_dev = len(jax.devices())
    assert n_dev == 8, f"expected 8 emulated CPU devices, got {n_dev}"
    return make_data_mesh(8)


def run_fleet(args, mesh, ckpt):
    from repro.core import Policy
    from repro.energy import (BatteryConfig, Bernoulli, ControlBounds,
                              FleetConfig, ServerController, run_controlled)
    from repro.energy.control import BudgetRule, CadenceRule
    from repro.energy.fleet import _run_fleet_scan

    n = args.clients
    proc = Bernoulli.create(n, prob=0.375, amount=1.25)
    bat = BatteryConfig(capacity=2.5, leak=0.0, init_charge=0.5)
    cfg = FleetConfig(num_clients=n, policy=Policy.SUSTAINABLE,
                      threshold=1.5, seed=3)
    # live rules + groups: the restored ControlState/trace must matter
    controller = ServerController(
        T0=5, E0=[1, 2, 4], groups=np.arange(n) % 3,
        bounds=ControlBounds(t_min=1, t_max=10, e_min=1, e_max=64),
        rules=(CadenceRule(), BudgetRule()))
    res, controller = run_controlled(
        proc, bat, 0.75, cfg, args.rounds, controller,
        control_every=args.control_every, mesh=mesh, pad_to=args.pad_to,
        backend=args.backend, checkpoint=ckpt, resume=args.resume,
        hist=args.hist)
    return res, controller, _run_fleet_scan


def run_serve(args, mesh, ckpt):
    from repro.energy import (BatteryConfig, Bernoulli, DecodeCostModel,
                              ServerController)
    from repro.energy.control import AdmissionRule, BudgetRule, CadenceRule
    from repro.serve import (BatteryGated, Constant, QoSSpec, ServeConfig,
                             run_serve_controlled)
    from repro.serve.fleet_serve import _run_serve_scan

    n = args.clients
    traffic = Constant.create(n, rate=2.0)
    harvest = Bernoulli.create(n, prob=0.375, amount=1.25)
    bat = BatteryConfig(capacity=2.5, leak=0.0, init_charge=0.5)
    cost = DecodeCostModel(2.0 ** -8, 2.0 ** -9, 2.0 ** -6)
    qos = QoSSpec(prompt_tokens=64.0, full_decode_tokens=128.0,
                  short_decode_tokens=32.0)
    controller = ServerController(
        T0=4, E0=4, admit0=1.0,
        rules=(AdmissionRule(), CadenceRule(), BudgetRule()))
    res, controller = run_serve_controlled(
        traffic, harvest, bat, cost, qos, BatteryGated.create(n),
        ServeConfig(num_clients=n, seed=5), args.rounds, controller,
        train_cost=0.25, control_every=args.control_every, mesh=mesh,
        pad_to=args.pad_to, backend=args.backend, checkpoint=ckpt,
        resume=args.resume, hist=args.hist)
    return res, controller, _run_serve_scan


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--kind", choices=["fleet", "serve"], required=True)
    p.add_argument("--backend", default="lax", choices=["lax", "pallas"])
    p.add_argument("--mesh", action="store_true")
    p.add_argument("--pad-to", type=int, default=None)
    p.add_argument("--clients", type=int, default=21)
    p.add_argument("--rounds", type=int, default=36)
    p.add_argument("--control-every", type=int, default=6)
    p.add_argument("--ckpt", default=None)
    p.add_argument("--out", default=None)
    p.add_argument("--resume", action="store_true")
    p.add_argument("--hist", action="store_true",
                   help="hist=True run: in-scan histograms + the carried "
                        "depletion streak ride the checkpoints (DESIGN.md "
                        "§14); kill-and-resume must stay bit-exact on them")
    p.add_argument("--kill-after-saves", type=int, default=None)
    p.add_argument("--signal", default="KILL", choices=sorted(SIGNALS))
    p.add_argument("--corrupt", default="none",
                   choices=["none", "truncate", "garbage"])
    args = p.parse_args()

    mesh = make_mesh(args.mesh)
    ckpt = None
    if args.ckpt:
        ckpt = KillingCheckpointer(
            args.ckpt, kill_after=args.kill_after_saves,
            sig=SIGNALS[args.signal], corrupt=args.corrupt)
    run = run_fleet if args.kind == "fleet" else run_serve
    res, controller, scan = run(args, mesh, ckpt)

    # the whole horizon — fresh or resumed — compiles ONE chunk program
    assert scan._cache_size() <= 1, \
        f"resume retraced the scan: {scan._cache_size()} cache entries"
    horizon = len(next(iter(res.stats.values())))
    assert horizon == args.rounds, (horizon, args.rounds)

    if args.out:
        payload = {"stat_" + k: np.asarray(v) for k, v in res.stats.items()}
        payload["final_charge"] = np.asarray(res.final_charge)
        if getattr(res, "final_streak", None) is not None:
            payload["final_streak"] = np.asarray(res.final_streak)
        payload.update({"ctl_" + k: v
                        for k, v in pack_controller(controller).items()})
        np.savez(args.out, **payload)
    print("resume child OK")


if __name__ == "__main__":
    main()
