"""The decode tick's roofline share, read as chat's is (step_mfu.chat.py):
over the window's ticks, each tick's least time (the larger of its
operations over peak and its weight plus live latent bytes over HBM
bandwidth, from the configuration's counts) over the summed `serve.decode`
spans of the program."""

from bench.harness import load_reader

read = load_reader("layer_metrics", "step_mfu.chat")
