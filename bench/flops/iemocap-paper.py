"""Model FLOPs of iemocap-paper: the audio LSTM-50 and the text LSTM-60."""
from bench.flops.layers import forward_per_sample  # noqa: F401
