"""End-to-end driver: federated GeoDoRA fine-tuning of a language model.

Default runs a CPU-sized config for a few rounds; pass --full to train the
~100M fedmm-small for a few hundred steps (slow on CPU, sized for a real
accelerator), or --arch to pick any assigned architecture (reduced).

    PYTHONPATH=src python examples/train_federated_lm.py
    PYTHONPATH=src python examples/train_federated_lm.py --full

Partial participation
---------------------
Real cross-silo rounds rarely field every node.  The engine samples a
reporting cohort per round ON DEVICE (the sampler state rides the fused
round blocks and checkpoints), non-reporters carry their state through
untouched, and the server averages Grams/precisions/side-cars over exactly
the cohort:

    # 2-of-K uniformly sampled cohort per round (compute tracks the
    # cohort size, not K — the cohort rows are gathered compactly)
    PYTHONPATH=src python examples/train_federated_lm.py \
        --participation uniform --cohort-size 2

    # straggler simulation: each node drops out with p=0.25 per round
    PYTHONPATH=src python examples/train_federated_lm.py \
        --participation dropout --dropout-rate 0.25

    # poll unreliable (low LAP-precision) nodes less often
    PYTHONPATH=src python examples/train_federated_lm.py \
        --participation precision --cohort-size 2

``--participation full`` (default) is bit-identical to the
pre-participation driver.  Everything composes with ``--block-size M``
(or ``--block-size auto``) fused round blocks and ``--warmup-rounds N``
round-indexed LR schedules.

Failure modes and recovery
--------------------------
``--participation async`` switches to the buffered staleness-aware
protocol: every node trains against the LAST global it received, finished
reports land in a server-side buffer after a sampled lag, and each round
the server averages whatever is fresh enough.  The failure simulator runs
ON DEVICE from a carried RNG state, so the whole fault schedule rides the
fused round blocks and is reproducible from ``--participation-seed``:

    # straggling reports: geometric lag, capped at 4 rounds; reports
    # older than 2 rounds get zero weight (bounded staleness)
    PYTHONPATH=src python examples/train_federated_lm.py \
        --participation async --lag-dist geometric --lag-p 0.5 \
        --max-lag 4 --max-staleness 2 --staleness cutoff

    # soft staleness discounting instead: weight ~ (1 + lag)^-alpha
    PYTHONPATH=src python examples/train_federated_lm.py \
        --participation async --lag-dist fixed --lag 1 \
        --staleness poly --staleness-alpha 1.0

    # crash-and-rejoin: 10% of online nodes crash per round (their
    # in-flight report is lost), crashed nodes rejoin with p=0.5
    PYTHONPATH=src python examples/train_federated_lm.py \
        --participation async --crash-rate 0.1 --rejoin-rate 0.5

    # byzantine/fault injection: node 1's reports are corrupted to NaN
    # on device; the quarantine guard zeroes its contribution and bumps
    # its per-node counter (printed per round) — the run stays finite
    PYTHONPATH=src python examples/train_federated_lm.py \
        --participation async --poison-nodes 1 --quarantine-norm 1e6

Quarantine triggers on non-finite report values OR an update norm above
``--quarantine-norm``; quarantined reports are dropped before they touch
the buffer, so one bad node can never poison the global average.

Crash recovery composes with the fused blocks: the library's
``Federation.run_rounds(..., checkpoint_path=..., checkpoint_every=N)``
streams checkpoints from INSIDE a compiled M-round block (an io_callback
state tap every N rounds), so a preempted run restores bit-identically
losing at most N rounds — see tests/test_async.py for the
kill-and-resume proof.
"""
import argparse
import sys

from repro.compile_cache import enable_compile_cache
from repro.launch.train import main as train_main


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="~100M params, 25 rounds x 8 local steps")
    ap.add_argument("--arch", default="fedmm-small")
    ap.add_argument("--participation", default="full",
                    choices=["full", "uniform", "precision", "dropout",
                             "async"])
    ap.add_argument("--cohort-size", type=int, default=None)
    ap.add_argument("--dropout-rate", type=float, default=0.25)
    # anything else (--block-size, --warmup-rounds, and the async flags
    # --lag-dist/--lag/--lag-p/--max-lag/--max-staleness/--staleness/
    # --staleness-alpha/--crash-rate/--rejoin-rate/--transient-rate/
    # --quarantine-norm/--poison-nodes) passes through to the underlying
    # repro.launch.train driver
    args, extra = ap.parse_known_args()
    part = ["--participation", args.participation,
            "--dropout-rate", str(args.dropout_rate)] + extra
    if args.cohort_size is not None:
        part += ["--cohort-size", str(args.cohort_size)]
    if args.full:
        train_main(["--arch", args.arch, "--rounds", "25",
                    "--local-steps", "8", "--batch", "8", "--seq", "512",
                    "--method", "geodora"] + part)
    else:
        train_main(["--arch", args.arch, "--tiny", "--rounds", "3",
                    "--local-steps", "4", "--batch", "4", "--seq", "128",
                    "--method", "geodora"] + part)


if __name__ == "__main__":
    enable_compile_cache()
    main()
