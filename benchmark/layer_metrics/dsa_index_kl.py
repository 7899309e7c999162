"""Per-layer metric ``dsa_index_kl``: the indexer's loss, nats a query
row: the mean over the window's samples and blocks of ``kl`` of the
``dsa:select`` counter (``dsa_kept_pairs_share`` says where it comes
from): ``KL(the heads' mean attention probabilities || softmax of the
indexer's scores over the selection)``, a sequence's mean over its rows.

Lower is a closer indexer AT THE SAME STATE OF THE MODEL: compare two
programs at the same seed and step count, never the start of a window
with its end.  Within a window it RISES (``first`` and ``last`` in the
extra are the means of the window's first and last tenth of the samples
of every block): the target is the model's own attention, near uniform
over a row's selection at the start and sharper with every step, and the
indexer follows it from behind.  That it follows is read against the
same run with the indexer frozen (no index-loss head, so no gradient
reaches it; builder's chip runs, PR 57, seed 2147483777, 62 steps, the
mean over the four blocks): trained 0.077 in the first tenth and 0.188
in the last (0.137 over the run), frozen 0.089 and 0.470 (0.218);
trained below frozen in 52 of the 61 steps after the first.  Against a
FIXED target plain descent on the loss lowers it step after step
(``test_the_index_loss_falls_against_a_fixed_target``,
``tests/test_keye_vl.py``).
Nothing where the program records no such counter."""
LAYER = "learned selection"
UNIT = "nats/row"
BETTER = "lower"
SOURCE = "program_counter"
DRIVERS = ("train_fit",)


def read(obs):
    import os
    import manifest
    shares = manifest.load_module(
        "layer_metrics", "dsa_kept_pairs_share",
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    by_block = [[r["kl"] for r in rows if "kl" in r]
                for rows in shares.window_tracks(obs).values()]
    by_block = [kl for kl in by_block if kl]
    if not by_block:
        return None

    def mean(rows):
        flat = [x for part in rows for x in part]
        return sum(flat) / len(flat)

    tenth = max(1, min(len(kl) for kl in by_block) // 10)
    return mean(by_block), {
        "samples": sum(len(kl) for kl in by_block), "blocks": len(by_block),
        "first": mean([kl[:tenth] for kl in by_block]),
        "last": mean([kl[-tenth:] for kl in by_block])}
