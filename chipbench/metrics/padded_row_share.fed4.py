"""``padded_row_share``, read the same way in the cell of four federated runtimes,
whose noisier numbers are held to bounds of their own."""
from chipbench.harness import load_reader

read = load_reader("padded_row_share")
