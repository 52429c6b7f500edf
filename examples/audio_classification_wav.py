"""Audio classification from WAV files: WavFileRecordReader decodes PCM
and emits spectrogram frames, an MLP classifies the tone (ref:
dl4j-examples audio classification over datavec-data-audio readers).

  JAX_PLATFORMS=cpu \
  python examples/audio_classification_wav.py
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import tempfile
import wave

import numpy as np

from deeplearning4j_tpu.etl import WavFileRecordReader
from deeplearning4j_tpu.learning import Adam
from deeplearning4j_tpu.nn import MultiLayerNetwork, NeuralNetConfiguration
from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer

RATE, N, FRAME = 8000, 2048, 256


def _write_wav(path, sig):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with wave.open(path, "wb") as w:
        w.setnchannels(1)
        w.setsampwidth(2)
        w.setframerate(RATE)
        w.writeframes((np.clip(sig, -1, 1) * 32767).astype("<i2")
                      .tobytes())


def _make_dataset(root, n_per_class=8, seed=0):
    """Two classes of real PCM audio: low tones (300-500 Hz) vs high
    tones (1200-1800 Hz), each with noise."""
    rs = np.random.RandomState(seed)
    t = np.arange(N) / RATE
    for i in range(n_per_class):
        f_lo = rs.uniform(300, 500)
        f_hi = rs.uniform(1200, 1800)
        noise = lambda: rs.randn(N) * 0.05
        _write_wav(os.path.join(root, "low", f"l{i}.wav"),
                   0.7 * np.sin(2 * np.pi * f_lo * t) + noise())
        _write_wav(os.path.join(root, "high", f"h{i}.wav"),
                   0.7 * np.sin(2 * np.pi * f_hi * t) + noise())


def main(quick: bool = False):
    with tempfile.TemporaryDirectory() as root:
        _make_dataset(root, n_per_class=4 if quick else 12)
        reader = WavFileRecordReader(root_dir=root, frame_length=FRAME,
                                     frame_step=FRAME // 2,
                                     spectrogram=True)
        feats, labels = [], []
        for spec, label in reader:
            feats.append(spec.mean(axis=0))     # average spectrum
            labels.append(label)
        x = np.stack(feats).astype(np.float32)
        x /= x.max()
        y = np.eye(len(reader.labels), dtype=np.float32)[labels]

        conf = (NeuralNetConfiguration.builder().seed(7)
                .updater(Adam(1e-2)).weight_init("xavier").list()
                .layer(DenseLayer(n_out=32, activation="relu"))
                .layer(OutputLayer(n_out=y.shape[1], loss="mcxent",
                                   activation="softmax"))
                .input_type_feed_forward(x.shape[1]).build())
        net = MultiLayerNetwork(conf).init()
        net.fit(x, y, epochs=30 if quick else 120)
        from deeplearning4j_tpu.datasets import ArrayDataSetIterator
        acc = net.evaluate(
            ArrayDataSetIterator(x, y, batch=len(x))).accuracy()
        print(f"tone classification accuracy: {acc:.3f} "
              f"({len(x)} clips, {x.shape[1]} spectrum bins)")
        return acc


if __name__ == "__main__":
    main()
