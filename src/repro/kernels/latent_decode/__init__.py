from repro.kernels.latent_decode.kernel import block_positions  # noqa: F401
from repro.kernels.latent_decode.ops import latent_decode  # noqa: F401
