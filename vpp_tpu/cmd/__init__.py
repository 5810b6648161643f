"""Process entry points and DI wiring ("flavors").

Reference analogs: flavors/contiv (plugin set + Inject,
contiv_flavor.go:70-191), cmd/contiv-agent/main.go (event loop +
SIGTERM close), flavors/ksr + cmd/contiv-ksr.

``ContivAgent`` loads on first use: vpp-tpu-init and the IO daemon
import this package and must stay JAX-free.
"""

from vpp_tpu.cmd.config import AgentConfig, load_config

__all__ = ["AgentConfig", "ContivAgent", "load_config"]


def __getattr__(name):
    if name == "ContivAgent":
        from vpp_tpu.cmd.agent import ContivAgent

        return ContivAgent
    raise AttributeError(name)
