"""Per-layer metric ``dsa_tiles_hit_share``: the share of the causal 512 x
512 tiles (``sa_config``'s ``q_chunk_size`` x ``kv_chunk_size``) that hold
at least one selected pair, ``sum(tiles_hit) / sum(tiles_causal)`` over
the window's samples of the ``dsa:select`` counter
(``dsa_kept_pairs_share`` says where it comes from).  It is what a
kernel that skips the tiles the selection leaves empty could win: at 100
every causal tile is visited and the attention kernels' time is the
causal mask's, whatever the selection keeps; lower is what tile skipping
would save.  Nothing where the program records no such counter."""
LAYER = "learned selection"
UNIT = "%"
BETTER = "lower"
SOURCE = "program_counter"
DRIVERS = ("train_fit",)


def read(obs):
    import os
    import manifest
    shares = manifest.load_module(
        "layer_metrics", "dsa_kept_pairs_share",
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    return shares.read_share(obs, "tiles_hit", "tiles_causal")
