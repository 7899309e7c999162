"""FleetSupervisor driver for the chaos tests (run in a subprocess so
the workers' FLEET_FINAL lines and the supervisor's stats land in one
capturable stdout).

Usage::

    python tests/_fleet_driver.py --ckpt DIR [--faults SPEC] [--on-loss M]

Runs a 2-worker fleet of ``tests/nightly/dist_fleet_worker.py`` and
prints ``FLEET_STATS <json>`` (the supervisor's report + the run rc) as
the last line.
"""
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("JAX_PLATFORMS", "cpu")


def main():
    args = sys.argv[1:]
    ckpt = args[args.index("--ckpt") + 1]
    faults = args[args.index("--faults") + 1] if "--faults" in args else None
    on_loss = args[args.index("--on-loss") + 1] \
        if "--on-loss" in args else "rejoin"
    from mxnet_tpu.dist import FleetSupervisor
    worker = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "nightly", "dist_fleet_worker.py")
    env = {"MXNET_FAULTS": faults} if faults else None
    sup = FleetSupervisor(
        [sys.executable, worker, "--ckpt", ckpt],
        nworkers=2, on_loss=on_loss, checkpoint_dir=ckpt,
        timeout_s=240, env=env)
    rc = sup.run()
    doc = sup.stats.report()
    doc["rc"] = rc
    print("FLEET_STATS %s" % json.dumps(doc), flush=True)
    sys.exit(rc)


if __name__ == "__main__":
    main()
