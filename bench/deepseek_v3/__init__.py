"""The benchmark's own parts for the DeepSeek-V3 block (latent attention,
routed and shared experts, leading dense layers): weights from the seed,
the plain reference, and the work counts. A configuration file names them
(``bench/runners/offline_serving_arch.py``)."""
