"""Per-layer metric ``peak_hbm_gib``: peak bytes on the fullest chip:
``memory_stats()``'s peak_bytes_in_use + peak_bytes_reserved (the second
holds XLA's temp space)."""
LAYER = "device"
UNIT = "GiB"
BETTER = "lower"
SOURCE = "program_counter"
DRIVERS = ("train_fit",)


def read(obs):
    return obs["memory"]["peak_bytes"] / 2.0 ** 30, {
        "in_use_gib": obs["memory"]["peak_in_use_bytes"] / 2.0 ** 30,
        "reserved_gib": obs["memory"]["peak_reserved_bytes"] / 2.0 ** 30}
