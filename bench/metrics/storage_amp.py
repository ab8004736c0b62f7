"""storage_amp: vectors the store holds over the corpus's vectors
(``VectorStore.sa()``), the copies the lattice bought."""


def read(run):
    return run.storage_amp
