"""Model FLOPs of crema_d-paper: the audio LSTM-50 and the image CNN."""
from bench.flops.layers import forward_per_sample  # noqa: F401
