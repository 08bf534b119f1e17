"""MCFuser reproduction: fused MBCI kernels + the serving/training system
around them."""
