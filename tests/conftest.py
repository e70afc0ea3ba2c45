"""Test configuration: force an 8-device virtual CPU mesh.

Mirrors the reference's "distributed without a cluster" strategy (Spark tests
run local[N] in-JVM, BaseSparkTest.java:89): multi-chip sharding is exercised
on N virtual CPU devices via --xla_force_host_platform_device_count, so the
full tp/dp test matrix runs on any host. The chip is exercised by
chip_smoke.py through the chip tool, not by the test suite.

The suite pins the platform itself (jax.config.update below, before the
backend initialises) so it runs the same whatever JAX_PLATFORMS says, and
hard-asserts the device count so a silent single-device mesh can never fake
a passing distributed suite.
"""
import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
assert jax.device_count() == 8, (
    f"Test suite requires the 8-device virtual CPU mesh, got "
    f"{jax.devices()} — platform forcing failed")

import pytest  # noqa: E402


@pytest.fixture
def rng_key():
    return jax.random.PRNGKey(0)


class _Front:
    """One of the two front ends of `Trainer.fit` (nn/stepping.py), built
    from the same two layers: the list network, or a two-vertex graph."""

    def __init__(self, kind: str):
        self.kind = kind

    @property
    def batch_type(self):
        from deeplearning4j_tpu.data.dataset import DataSet, MultiDataSet
        return DataSet if self.kind == "mln" else MultiDataSet

    @property
    def cast_name(self):
        """The method that casts a batch's features to the network's dtype
        on the way into a step."""
        return "_cast_features" if self.kind == "mln" else "_pack_inputs"

    def net(self, seed=7, n_in=4, hidden=16, classes=3, updater=None,
            activation="relu", dtype=None):
        import jax.numpy as jnp
        from deeplearning4j_tpu import (Adam, ComputationGraph, DenseLayer,
                                        InputType, MultiLayerNetwork,
                                        NeuralNetConfiguration, OutputLayer)
        how = {} if dtype is None else {"dtype": jnp.dtype(dtype)}
        b = (NeuralNetConfiguration.builder().seed(seed)
             .updater(updater or Adam(0.05)))
        dense = DenseLayer(n_out=hidden, activation=activation)
        out = OutputLayer(n_out=classes, activation="softmax", loss="mcxent")
        if self.kind == "mln":
            return MultiLayerNetwork(
                b.list().layer(dense).layer(out)
                .set_input_type(InputType.feed_forward(n_in)).build()
                ).init(**how)
        return ComputationGraph(
            b.graph_builder().add_inputs("in")
            .add_layer("dense", dense, "in").add_layer("out", out, "dense")
            .set_outputs("out")
            .set_input_types(InputType.feed_forward(n_in)).build()
            ).init(**how)

    def tbptt_net(self, seed=5, n_in=4, window=5):
        """The same pair under truncated BPTT: an LSTM and a
        time-distributed output layer."""
        from deeplearning4j_tpu import (Adam, ComputationGraph, GravesLSTM,
                                        InputType, MultiLayerNetwork,
                                        NeuralNetConfiguration,
                                        RnnOutputLayer)
        from deeplearning4j_tpu.nn.conf.builders import BackpropType
        b = NeuralNetConfiguration.builder().seed(seed).updater(Adam(0.01))
        lstm = GravesLSTM(n_out=8, activation="tanh")
        out = RnnOutputLayer(n_out=3, activation="softmax", loss="mcxent")
        if self.kind == "mln":
            return MultiLayerNetwork(
                b.list().layer(lstm).layer(out)
                .set_input_type(InputType.recurrent(n_in))
                .backprop_type(BackpropType.TRUNCATED_BPTT)
                .tbptt_fwd_length(window).tbptt_back_length(window)
                .build()).init()
        return ComputationGraph(
            b.graph_builder().add_inputs("in")
            .add_layer("lstm", lstm, "in").add_layer("out", out, "lstm")
            .set_outputs("out")
            .backprop_type(BackpropType.TRUNCATED_BPTT)
            .tbptt_fwd_length(window)
            .set_input_types(InputType.recurrent(n_in)).build()).init()

    def first_layer(self, net):
        return net.layers[0] if self.kind == "mln" \
            else net.conf.nodes["dense"].layer


@pytest.fixture(params=["mln", "graph"])
def front(request):
    return _Front(request.param)
