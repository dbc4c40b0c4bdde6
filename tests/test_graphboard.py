"""Graphboard renders the ResNet train graph and serves it
(reference ``python/graphboard/graph2fig.py:11-31``)."""
import os
import urllib.request
import xml.etree.ElementTree as ET

import numpy as np

import hetu_tpu as ht
from hetu_tpu import graphboard
from hetu_tpu.utils import import_example_models as _import_example_models


def _resnet_executor():
    models = _import_example_models("cnn")
    x = ht.Variable(name="x", trainable=False)
    y_ = ht.Variable(name="y", trainable=False)
    loss, y = models.resnet18(x, y_, 10)
    train_op = ht.optim.SGDOptimizer(0.1).minimize(loss)
    return ht.Executor({"train": [loss, train_op]}, ctx=ht.cpu(0), seed=0)


def test_graphboard_renders_resnet_train_graph(tmp_path):
    ex = _resnet_executor()
    out = graphboard.render(ex, name="train", out_dir=str(tmp_path / "gb"))
    svg_path = os.path.join(out, "output.svg")
    dot_path = os.path.join(out, "output.dot")
    assert os.path.exists(svg_path) and os.path.exists(dot_path)

    # valid XML, with one rect per topo node (+1 background)
    root = ET.parse(svg_path).getroot()
    ns = "{http://www.w3.org/2000/svg}"
    rects = root.iter(f"{ns}rect")
    topo = ex.subexecutors["train"].topo
    assert sum(1 for _ in rects) == len(topo) + 1
    svg_text = open(svg_path).read()
    assert "Conv2d" in svg_text and "Optimizer" in svg_text

    dot = open(dot_path).read()
    assert dot.startswith("digraph")
    n_edges = sum(len(n.inputs) for n in topo)
    assert dot.count(" -> ") == n_edges


def test_graphboard_serves_http(tmp_path):
    ex = _resnet_executor()
    url = graphboard.show(ex, port=19997, name="train",
                          out_dir=str(tmp_path / "gb"))
    try:
        page = urllib.request.urlopen(url, timeout=10).read().decode()
        assert "graphboard" in page and "<svg" in page
        svg = urllib.request.urlopen(url + "output.svg", timeout=10).read()
        assert b"Conv2d" in svg
    finally:
        graphboard.close()
