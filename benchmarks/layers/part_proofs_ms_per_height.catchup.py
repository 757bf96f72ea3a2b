"""`types.part_set_proofs` per applied height: the part set's root and every
part's proof (the part of `blocksync.part_set` that is not the encoding)."""
from loadedlib import per_height_ms


def read(obs, run):
    return per_height_ms(obs, "types.part_set_proofs")
