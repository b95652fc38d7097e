"""``dispatch_cpu_us_per_token``, read the same way in the cell of four federated runtimes,
whose noisier numbers are held to bounds of their own."""
from chipbench.harness import load_reader

read = load_reader("dispatch_cpu_us_per_token")
