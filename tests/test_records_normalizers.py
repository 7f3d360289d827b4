"""Record-reader adapters + normalizer tests (reference
datasets/datavec/RecordReaderDataSetIterator semantics and ND4J
NormalizerStandardize/MinMaxScaler behavior; preprocessor.bin persistence per
ModelSerializer.java:94-99)."""

import numpy as np
import pytest

from deeplearning4j_tpu.datasets.dataset import ArrayDataSetIterator, DataSet
from deeplearning4j_tpu.datasets.normalizers import (
    DataNormalization, ImagePreProcessingScaler, NormalizerMinMaxScaler,
    NormalizerStandardize)
from deeplearning4j_tpu.datasets.records import (
    ALIGN_END, ALIGN_START, CollectionRecordReader,
    CollectionSequenceRecordReader, CSVRecordReader, CSVSequenceRecordReader,
    LineRecordReader, RecordReaderDataSetIterator,
    RecordReaderMultiDataSetIterator, SequenceRecordReaderDataSetIterator)


class TestRecordReaders:
    def test_csv_classification_one_hot(self):
        text = "1.0,2.0,0\n3.0,4.0,2\n5.0,6.0,1\n"
        rr = CSVRecordReader(text=text)
        it = RecordReaderDataSetIterator(rr, batch_size=2, label_index=2,
                                         num_possible_labels=3)
        ds = next(iter(it))
        assert ds.features.shape == (2, 2)
        np.testing.assert_allclose(ds.features, [[1, 2], [3, 4]])
        np.testing.assert_allclose(ds.labels, [[1, 0, 0], [0, 0, 1]])
        ds2 = next(it)
        assert ds2.features.shape == (1, 2)
        with pytest.raises(StopIteration):
            next(it)

    def test_csv_regression_range(self):
        text = "1,2,10,20\n3,4,30,40\n"
        rr = CSVRecordReader(text=text)
        it = RecordReaderDataSetIterator(rr, batch_size=2, label_index=2,
                                         label_index_to=3, regression=True)
        ds = next(iter(it))
        np.testing.assert_allclose(ds.features, [[1, 2], [3, 4]])
        np.testing.assert_allclose(ds.labels, [[10, 20], [30, 40]])

    def test_csv_file_and_skip_lines(self, tmp_path):
        p = tmp_path / "data.csv"
        p.write_text("header,row,x\n1,2,0\n3,4,1\n")
        rr = CSVRecordReader(path=str(p), skip_lines=1)
        recs = list(rr)
        assert recs == [[1.0, 2.0, 0.0], [3.0, 4.0, 1.0]]

    def test_line_and_collection_readers(self):
        lr = LineRecordReader(lines=["a b", "c d"])
        assert list(lr) == [["a b"], ["c d"]]
        cr = CollectionRecordReader([[1, 2], [3, 4]])
        assert list(cr) == [[1, 2], [3, 4]]

    def test_out_of_range_label_raises(self):
        rr = CollectionRecordReader([[1.0, 2.0, -1]])
        it = RecordReaderDataSetIterator(rr, batch_size=1, label_index=2,
                                         num_possible_labels=3)
        with pytest.raises(ValueError, match="outside"):
            next(iter(it))
        rr2 = CollectionRecordReader([[1.0, 2.0, 5]])
        it2 = RecordReaderDataSetIterator(rr2, batch_size=1, label_index=2,
                                          num_possible_labels=3)
        with pytest.raises(ValueError, match="outside"):
            next(iter(it2))

    def test_file_readers_close_handles(self, tmp_path):
        p = tmp_path / "d.csv"
        p.write_text("1,2\n3,4\n")
        rr = CSVRecordReader(path=str(p))
        assert len(list(rr)) == 2
        assert rr._fh is None  # closed on exhaustion
        rr.reset()
        next(iter(rr))
        rr.close()
        assert rr._fh is None

    def test_max_num_batches(self):
        rr = CollectionRecordReader([[i, 0] for i in range(10)])
        it = RecordReaderDataSetIterator(rr, batch_size=2, label_index=1,
                                         num_possible_labels=2, max_num_batches=2)
        assert len(list(it)) == 2


class TestSequenceIterators:
    def test_single_reader_equal_length(self):
        seqs = [[[0.1, 0.2, 0], [0.3, 0.4, 1]],
                [[0.5, 0.6, 1], [0.7, 0.8, 0]]]
        rr = CollectionSequenceRecordReader(seqs)
        it = SequenceRecordReaderDataSetIterator(rr, batch_size=2,
                                                 num_possible_labels=2,
                                                 label_index=2)
        ds = next(iter(it))
        assert ds.features.shape == (2, 2, 2)
        assert ds.labels.shape == (2, 2, 2)
        np.testing.assert_allclose(ds.labels[0], [[1, 0], [0, 1]])

    def test_two_readers_align_end_masks(self):
        fseqs = [[[1.0], [2.0], [3.0]], [[4.0]]]
        lseqs = [[[0]], [[1]]]
        it = SequenceRecordReaderDataSetIterator(
            CollectionSequenceRecordReader(fseqs), batch_size=2,
            num_possible_labels=2,
            labels_reader=CollectionSequenceRecordReader(lseqs),
            alignment=ALIGN_END)
        ds = next(iter(it))
        assert ds.features.shape == (2, 3, 1)
        # labels align at last step; mask marks only that step for seq 0
        assert ds.labels_mask is not None
        np.testing.assert_allclose(ds.labels_mask[0], [0, 0, 1])
        # second (short) feature seq padded at start under ALIGN_END
        np.testing.assert_allclose(ds.features[1, :, 0], [0, 0, 4.0])

    def test_align_start(self):
        fseqs = [[[1.0], [2.0]], [[3.0]]]
        lseqs = [[[0]], [[1]]]
        it = SequenceRecordReaderDataSetIterator(
            CollectionSequenceRecordReader(fseqs), batch_size=2,
            num_possible_labels=2,
            labels_reader=CollectionSequenceRecordReader(lseqs),
            alignment=ALIGN_START)
        ds = next(iter(it))
        np.testing.assert_allclose(ds.labels_mask[0], [1, 0])

    def test_single_reader_variable_length_keeps_masks(self):
        # regression: padding exists, so masks must NOT be dropped even though
        # feature and label masks are equal
        seqs = [[[0.1, 0], [0.2, 1], [0.3, 0]], [[0.4, 1]]]
        rr = CollectionSequenceRecordReader(seqs)
        it = SequenceRecordReaderDataSetIterator(rr, batch_size=2,
                                                 num_possible_labels=2,
                                                 label_index=1,
                                                 alignment=ALIGN_START)
        ds = next(iter(it))
        assert ds.features_mask is not None and ds.labels_mask is not None
        np.testing.assert_allclose(ds.features_mask[1], [1, 0, 0])

    def test_unlabeled_sequences(self):
        seqs = [[[0.1, 0.2], [0.3, 0.4]], [[0.5, 0.6]]]
        rr = CollectionSequenceRecordReader(seqs)
        it = SequenceRecordReaderDataSetIterator(rr, batch_size=2)
        ds = next(iter(it))
        assert ds.labels is None
        assert ds.features.shape == (2, 2, 2)
        assert ds.features_mask is not None

    def test_mismatched_reader_lengths_raise(self):
        fseqs = [[[1.0]], [[2.0]], [[3.0]]]
        lseqs = [[[0]], [[1]]]
        it = SequenceRecordReaderDataSetIterator(
            CollectionSequenceRecordReader(fseqs), batch_size=2,
            num_possible_labels=2,
            labels_reader=CollectionSequenceRecordReader(lseqs))
        batches = iter(it)
        next(batches)
        with pytest.raises(ValueError, match="exhausted"):
            next(batches)

    def test_csv_sequence_files(self, tmp_path):
        p1 = tmp_path / "s1.csv"
        p1.write_text("1,0\n2,1\n")
        p2 = tmp_path / "s2.csv"
        p2.write_text("3,1\n4,0\n")
        rr = CSVSequenceRecordReader([str(p1), str(p2)])
        it = SequenceRecordReaderDataSetIterator(rr, batch_size=2,
                                                 num_possible_labels=2,
                                                 label_index=1)
        ds = next(iter(it))
        assert ds.features.shape == (2, 2, 1)


class TestMultiDataSetIterator:
    def test_named_readers_inputs_outputs(self):
        rr = CollectionRecordReader([[1, 2, 3, 0], [4, 5, 6, 1]])
        it = (RecordReaderMultiDataSetIterator(batch_size=2)
              .add_reader("r", rr)
              .add_input("r", 0, 1)
              .add_output("r", 2, 2)
              .add_output_one_hot("r", 3, 2))
        mds = next(iter(it))
        assert len(mds.features) == 1 and len(mds.labels) == 2
        np.testing.assert_allclose(mds.features[0], [[1, 2], [4, 5]])
        np.testing.assert_allclose(mds.labels[0], [[3], [6]])
        np.testing.assert_allclose(mds.labels[1], [[1, 0], [0, 1]])

    def test_mismatched_named_readers_raise(self):
        it = (RecordReaderMultiDataSetIterator(batch_size=4)
              .add_reader("a", CollectionRecordReader([[1], [2], [3]]))
              .add_reader("b", CollectionRecordReader([[1], [2]]))
              .add_input("a").add_output("b"))
        with pytest.raises(ValueError, match="mismatched record counts"):
            next(iter(it))


class TestNormalizers:
    def test_standardize_fit_transform_revert(self, rng):
        X = rng.randn(200, 5) * 3.0 + 7.0
        it = ArrayDataSetIterator(X, np.zeros((200, 1)), batch_size=32)
        norm = NormalizerStandardize().fit(it)
        ds = DataSet(X.copy(), None)
        norm.pre_process(ds)
        np.testing.assert_allclose(ds.features.mean(axis=0), 0, atol=1e-5)
        np.testing.assert_allclose(ds.features.std(axis=0), 1, atol=1e-4)
        norm.revert(ds)
        np.testing.assert_allclose(ds.features, X, atol=1e-4)

    def test_standardize_streaming_matches_full(self, rng):
        X = rng.randn(100, 3)
        it = ArrayDataSetIterator(X, np.zeros((100, 1)), batch_size=7)
        norm = NormalizerStandardize().fit(it)
        np.testing.assert_allclose(norm.mean, X.mean(axis=0), atol=1e-10)
        np.testing.assert_allclose(norm.std, X.std(axis=0), atol=1e-10)

    def test_standardize_labels_and_masked_rnn(self, rng):
        X = rng.randn(4, 6, 2)
        mask = np.zeros((4, 6), np.float32)
        mask[:, :3] = 1.0
        ds = DataSet(X.copy(), None, features_mask=mask)
        norm = NormalizerStandardize().fit(ds)
        valid = X[:, :3, :].reshape(-1, 2)
        np.testing.assert_allclose(norm.mean, valid.mean(axis=0), atol=1e-10)

    def test_minmax(self, rng):
        X = rng.rand(50, 4) * 10 - 5
        norm = NormalizerMinMaxScaler().fit(DataSet(X.copy(), None))
        ds = DataSet(X.copy(), None)
        norm.pre_process(ds)
        assert ds.features.min() >= -1e-6 and ds.features.max() <= 1 + 1e-6
        norm.revert(ds)
        np.testing.assert_allclose(ds.features, X, atol=1e-4)

    def test_image_scaler(self):
        X = np.asarray([[0.0, 127.5, 255.0]])
        ds = DataSet(X, None)
        ImagePreProcessingScaler().pre_process(ds)
        np.testing.assert_allclose(ds.features, [[0, 0.5, 1.0]])

    def test_labeled_image_records_require_num_labels(self):
        rr = CollectionRecordReader([])
        rr.records = [[np.zeros((2, 2, 1), np.float32), 1.0]]
        it = RecordReaderDataSetIterator(rr, batch_size=1)
        with pytest.raises(ValueError, match="num_possible_labels"):
            next(iter(it))

    def test_minmax_labels(self, rng):
        X = rng.rand(20, 3)
        Y = rng.rand(20, 2) * 10
        norm = NormalizerMinMaxScaler().fit_label(True).fit(DataSet(X.copy(), Y.copy()))
        ds = DataSet(X.copy(), Y.copy())
        norm.pre_process(ds)
        assert ds.labels.max() <= 1 + 1e-6 and ds.labels.min() >= -1e-6
        norm.revert(ds)
        np.testing.assert_allclose(ds.labels, Y, atol=1e-4)

    def test_list_iterator_no_double_normalize(self, rng):
        from deeplearning4j_tpu.datasets.dataset import ListDataSetIterator
        X = rng.rand(6, 3) * 255
        ds_list = [DataSet(X[:3].copy(), None), DataSet(X[3:].copy(), None)]
        it = ListDataSetIterator(ds_list)
        it.set_pre_processor(ImagePreProcessingScaler())
        first_epoch = [np.array(d.features) for d in it]
        second_epoch = [np.array(d.features) for d in it]
        for a, b in zip(first_epoch, second_epoch):
            np.testing.assert_allclose(a, b)
        assert ds_list[0].features.max() > 1.0  # originals untouched

    def test_wrapper_over_list_no_double_normalize(self, rng):
        from deeplearning4j_tpu.datasets.async_iterator import MultipleEpochsIterator
        from deeplearning4j_tpu.datasets.dataset import ListDataSetIterator
        X = rng.rand(4, 3) * 255
        ds_list = [DataSet(X.copy(), None)]
        it = MultipleEpochsIterator(3, ListDataSetIterator(ds_list))
        it.set_pre_processor(ImagePreProcessingScaler())
        seen = [np.array(d.features) for d in it]
        assert len(seen) == 3
        for a in seen[1:]:
            np.testing.assert_allclose(seen[0], a)
        assert ds_list[0].features.max() > 1.0

    def test_async_iterator_applies_pp_in_worker(self, rng):
        from deeplearning4j_tpu.datasets.async_iterator import AsyncDataSetIterator
        X = rng.rand(8, 3) * 255
        base = ArrayDataSetIterator(X, np.zeros((8, 1)), batch_size=4)
        it = AsyncDataSetIterator(base)
        it.set_pre_processor(ImagePreProcessingScaler())
        for ds in it:
            assert ds.features.max() <= 1.0

    def test_add_normalizer_replaces_existing(self, tmp_path, rng):
        from deeplearning4j_tpu import NeuralNetConfiguration
        from deeplearning4j_tpu.models.multi_layer_network import MultiLayerNetwork
        from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
        from deeplearning4j_tpu.utils import model_serializer
        import zipfile

        conf = (NeuralNetConfiguration.Builder().seed(1).list()
                .layer(DenseLayer(n_in=2, n_out=3))
                .layer(OutputLayer(n_out=2, activation="softmax", loss="mcxent"))
                .build())
        net = MultiLayerNetwork(conf).init()
        path = str(tmp_path / "model.zip")
        model_serializer.write_model(net, path)
        model_serializer.add_normalizer_to_model(
            path, NormalizerMinMaxScaler().fit(DataSet(rng.rand(10, 2), None)))
        model_serializer.add_normalizer_to_model(path, ImagePreProcessingScaler())
        with zipfile.ZipFile(path) as z:
            assert z.namelist().count(model_serializer.NORMALIZER_NAME) == 1
        assert isinstance(model_serializer.restore_normalizer_from_file(path),
                          ImagePreProcessingScaler)
        assert model_serializer.restore_model(path) is not None

    def test_fetcher_iterators_honor_pre_processor(self):
        from deeplearning4j_tpu.datasets.fetchers import MnistDataSetIterator
        it = MnistDataSetIterator(batch_size=4, train=True, seed=7,
                                  num_examples=64)
        it.set_pre_processor(ImagePreProcessingScaler(a=-1.0, b=1.0, max_pixel=1.0))
        ds = next(iter(it))
        assert ds.features.min() >= -1.0 and ds.features.max() <= 1.0

    def test_iterator_pre_processor_hook(self, rng):
        X = rng.rand(10, 3) * 255
        it = ArrayDataSetIterator(X, np.zeros((10, 1)), batch_size=5)
        it.set_pre_processor(ImagePreProcessingScaler())
        ds = next(iter(it))
        assert ds.features.max() <= 1.0

    def test_serialization_roundtrip(self, rng):
        X = rng.randn(30, 4)
        norm = NormalizerStandardize().fit(DataSet(X.copy(), None))
        restored = DataNormalization.from_bytes(norm.to_bytes())
        assert isinstance(restored, NormalizerStandardize)
        np.testing.assert_allclose(restored.mean, norm.mean)
        a, b = DataSet(X.copy(), None), DataSet(X.copy(), None)
        norm.pre_process(a)
        restored.pre_process(b)
        np.testing.assert_allclose(a.features, b.features)

    def test_checkpoint_preprocessor_bin(self, tmp_path, rng):
        from deeplearning4j_tpu import NeuralNetConfiguration
        from deeplearning4j_tpu.models.multi_layer_network import MultiLayerNetwork
        from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
        from deeplearning4j_tpu.utils import model_serializer

        conf = (NeuralNetConfiguration.Builder().seed(1).list()
                .layer(DenseLayer(n_in=4, n_out=5))
                .layer(OutputLayer(n_out=3, activation="softmax", loss="mcxent"))
                .build())
        net = MultiLayerNetwork(conf).init()
        norm = NormalizerStandardize().fit(DataSet(rng.randn(20, 4), None))
        path = str(tmp_path / "model.zip")
        model_serializer.write_model(net, path, normalizer=norm)
        back = model_serializer.restore_normalizer_from_file(path)
        np.testing.assert_allclose(back.mean, norm.mean)
        assert model_serializer.restore_model(path) is not None

    def test_add_normalizer_to_existing_checkpoint(self, tmp_path, rng):
        from deeplearning4j_tpu import NeuralNetConfiguration
        from deeplearning4j_tpu.models.multi_layer_network import MultiLayerNetwork
        from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
        from deeplearning4j_tpu.utils import model_serializer

        conf = (NeuralNetConfiguration.Builder().seed(1).list()
                .layer(DenseLayer(n_in=2, n_out=3))
                .layer(OutputLayer(n_out=2, activation="softmax", loss="mcxent"))
                .build())
        net = MultiLayerNetwork(conf).init()
        path = str(tmp_path / "model.zip")
        model_serializer.write_model(net, path)
        assert model_serializer.restore_normalizer_from_file(path) is None
        model_serializer.add_normalizer_to_model(
            path, NormalizerMinMaxScaler().fit(DataSet(rng.rand(10, 2), None)))
        assert isinstance(model_serializer.restore_normalizer_from_file(path),
                          NormalizerMinMaxScaler)


class TestMagicQueue:
    """parallelism/MagicQueue.java parity: per-device buckets, round-robin
    producer fan-out, device-affinity consumption."""

    def test_round_robin_and_affinity(self):
        from deeplearning4j_tpu.datasets.magic_queue import MagicQueue
        q = MagicQueue(3)
        for i in range(9):
            q.add(i)
        assert q.size() == 9
        assert [q.take(0) for _ in range(3)] == [0, 3, 6]
        assert [q.take(1) for _ in range(3)] == [1, 4, 7]
        assert q.size(2) == 3 and q.size() == 3
        assert q.poll(0) is None                # empty bucket -> None
        q.add_for(0, "direct")
        assert q.take(0) == "direct"

    def test_concurrent_producers_consumers(self):
        import threading
        from deeplearning4j_tpu.datasets.magic_queue import MagicQueue
        q = MagicQueue(2, capacity_per_device=4)
        got = {0: [], 1: []}

        def consume(dev):
            for _ in range(20):
                got[dev].append(q.take(dev))

        threads = [threading.Thread(target=consume, args=(d,), daemon=True)
                   for d in (0, 1)]
        for t in threads:
            t.start()
        for i in range(40):
            q.add(i)
        for t in threads:
            t.join(timeout=10)
        assert not any(t.is_alive() for t in threads), "consumer hung"
        assert sorted(got[0] + got[1]) == list(range(40))
        assert len(got[0]) == len(got[1]) == 20


class TestAsyncStaging:
    """Super-batch staging (stage>1): one combined device transfer per K
    batches, values/order identical to unstaged iteration."""

    def _base(self, rng, n=44, b=4, with_masks=False):
        X = rng.rand(n, 3).astype(np.float32)
        Y = rng.rand(n, 2).astype(np.float32)
        from deeplearning4j_tpu.datasets.dataset import ListDataSetIterator
        sets = []
        for i in range(0, n, b):
            fm = np.ones((min(b, n - i), 1), np.float32) if with_masks else None
            sets.append(DataSet(X[i:i+b], Y[i:i+b], features_mask=fm))
        return X, Y, ListDataSetIterator(sets)

    def test_values_and_order_preserved(self, rng):
        from deeplearning4j_tpu.datasets.async_iterator import AsyncDataSetIterator
        X, Y, base = self._base(rng)          # 11 batches: 8 staged + 3 tail
        it = AsyncDataSetIterator(base, stage=8)
        got_x = np.concatenate([np.asarray(d.features) for d in it])
        np.testing.assert_allclose(got_x, X, atol=1e-7)

    def test_batches_arrive_on_device(self, rng):
        import jax
        from deeplearning4j_tpu.datasets.async_iterator import AsyncDataSetIterator
        _, _, base = self._base(rng, n=16)
        out = list(AsyncDataSetIterator(base, stage=4))
        assert all(isinstance(d.features, jax.Array) for d in out)

    def test_masked_batches_fall_back(self, rng):
        from deeplearning4j_tpu.datasets.async_iterator import AsyncDataSetIterator
        X, Y, base = self._base(rng, with_masks=True)
        out = list(AsyncDataSetIterator(base, stage=8))
        assert len(out) == 11
        assert all(d.features_mask is not None for d in out)
        np.testing.assert_allclose(
            np.concatenate([np.asarray(d.features) for d in out]), X, atol=1e-7)

    def test_fit_through_staged_iterator_trains(self, rng):
        from deeplearning4j_tpu import NeuralNetConfiguration
        from deeplearning4j_tpu.models.multi_layer_network import MultiLayerNetwork
        from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
        from deeplearning4j_tpu.datasets.dataset import ListDataSetIterator
        conf = (NeuralNetConfiguration.Builder().seed(1).learning_rate(0.1)
                .updater("adam").list()
                .layer(DenseLayer(n_in=4, n_out=16, activation="relu"))
                .layer(OutputLayer(n_out=2, activation="softmax", loss="mcxent"))
                .build())
        net = MultiLayerNetwork(conf).init()
        X = rng.rand(128, 4).astype(np.float32)
        y = (X[:, 0] > 0.5).astype(int)
        Y = np.eye(2, dtype=np.float32)[y]
        sets = [DataSet(X[i:i+16], Y[i:i+16]) for i in range(0, 128, 16)]
        net.fit(ListDataSetIterator(sets), epochs=25)    # async stage=8 path
        score = float(net.score_)
        assert np.isfinite(score) and score < 0.45

    def test_device_resident_batches_not_round_tripped(self, rng):
        """Pre-staged (jax.Array) DataSets must not be downloaded to host
        for concatenation — they bypass staging."""
        import jax.numpy as jnp
        from deeplearning4j_tpu.datasets.async_iterator import AsyncDataSetIterator
        from deeplearning4j_tpu.datasets.dataset import ListDataSetIterator
        sets = [DataSet(jnp.asarray(rng.rand(4, 3).astype(np.float32)),
                        jnp.asarray(rng.rand(4, 2).astype(np.float32)))
                for _ in range(6)]
        out = list(AsyncDataSetIterator(ListDataSetIterator(sets), stage=4))
        assert len(out) == 6
        for got, want in zip(out, sets):
            np.testing.assert_allclose(np.asarray(got.features),
                                       np.asarray(want.features))

    def test_device_transfers_happen_on_consumer_thread_only(self, rng,
                                                             monkeypatch):
        """The prefetch worker must never call jax.device_put: every
        device op is issued from the consumer thread (the G010 contract).
        Staged transfers are deferred to the consumer thread."""
        import threading

        import jax
        from deeplearning4j_tpu.datasets import async_iterator as ai
        from deeplearning4j_tpu.datasets.dataset import ListDataSetIterator

        callers = []
        real_put = jax.device_put

        def spy(x, *a, **k):
            callers.append(threading.get_ident())
            return real_put(x, *a, **k)

        monkeypatch.setattr(ai.jax, "device_put", spy)
        _, _, base = self._base(rng, n=44)     # staged groups + tail
        out = list(ai.AsyncDataSetIterator(base, stage=8))
        assert len(out) == 11
        assert callers, "staging should device_put at least once"
        assert set(callers) == {threading.get_ident()}

    def test_sharded_staging_lands_on_the_mesh(self, rng):
        """With an explicit sharding (the ParallelWrapper contract) every
        emitted batch must be device-put WITH that sharding — and still on
        the consumer thread only."""
        import jax
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from deeplearning4j_tpu.datasets.async_iterator import (
            AsyncDataSetIterator)
        from deeplearning4j_tpu.datasets.dataset import ListDataSetIterator

        mesh = Mesh(np.array(jax.devices()[:8]), ("dp",))
        sharding = NamedSharding(mesh, P("dp"))
        sets = [DataSet(rng.rand(16, 3).astype(np.float32),
                        rng.rand(16, 2).astype(np.float32))
                for _ in range(5)]
        out = list(AsyncDataSetIterator(ListDataSetIterator(sets),
                                        sharding=sharding, stage=4))
        assert len(out) == 5
        for got, want in zip(out, sets):
            assert got.features.sharding == sharding
            np.testing.assert_allclose(np.asarray(got.features),
                                       want.features, atol=1e-7)

    def test_mismatched_label_shapes_do_not_stage_together(self, rng):
        """Equal feature shapes but different label widths must not be
        concatenated into one super-batch."""
        from deeplearning4j_tpu.datasets.async_iterator import AsyncDataSetIterator
        from deeplearning4j_tpu.datasets.dataset import ListDataSetIterator
        sets = [DataSet(rng.rand(4, 3).astype(np.float32),
                        rng.rand(4, 2 + (i % 2)).astype(np.float32))
                for i in range(6)]
        out = list(AsyncDataSetIterator(ListDataSetIterator(sets), stage=4))
        assert [d.labels.shape[1] for d in out] == [2, 3, 2, 3, 2, 3]

    def test_multidataset_staging(self, rng):
        """MultiDataSet batches (CG's data contract) stage per array
        stream; values/order preserved incl. the tail group."""
        import jax
        from deeplearning4j_tpu.datasets.async_iterator import AsyncDataSetIterator
        from deeplearning4j_tpu.datasets.dataset import MultiDataSet

        class _ListMulti:
            def __init__(self, items): self.items = items
            def __iter__(self): return iter(self.items)

        X1 = rng.rand(44, 3).astype(np.float32)
        X2 = rng.rand(44, 5).astype(np.float32)
        Y = rng.rand(44, 2).astype(np.float32)
        sets = [MultiDataSet([X1[i:i+4], X2[i:i+4]], [Y[i:i+4]])
                for i in range(0, 44, 4)]
        out = list(AsyncDataSetIterator(_ListMulti(sets), stage=8))
        assert len(out) == 11
        assert all(isinstance(d.features[0], jax.Array) for d in out)
        np.testing.assert_allclose(
            np.concatenate([np.asarray(d.features[1]) for d in out]), X2,
            atol=1e-7)
        np.testing.assert_allclose(
            np.concatenate([np.asarray(d.labels[0]) for d in out]), Y,
            atol=1e-7)

    def test_multidataset_preprocessor_through_async(self, rng):
        """A pre-processor on the async wrapper must handle MultiDataSet
        batches (the wrapper serves both batch kinds)."""
        from deeplearning4j_tpu.datasets.async_iterator import AsyncDataSetIterator
        from deeplearning4j_tpu.datasets.dataset import MultiDataSet

        class _ListMulti:
            def __init__(self, items): self.items = items
            def __iter__(self): return iter(self.items)

        class _Scale:
            def pre_process(self, mds):
                mds.features = [f / 255.0 for f in mds.features]

        sets = [MultiDataSet([rng.rand(4, 3).astype(np.float32) * 255],
                             [rng.rand(4, 2).astype(np.float32)])
                for _ in range(4)]
        it = AsyncDataSetIterator(_ListMulti(sets), stage=2)
        it.set_pre_processor(_Scale())
        out = list(it)
        assert len(out) == 4
        assert all(float(np.asarray(d.features[0]).max()) <= 1.0 for d in out)


class TestAsyncByteBudget:
    def test_tiny_byte_budget_completes_without_deadlock(self, rng,
                                                         monkeypatch):
        """stage_bytes below one batch forces group-target 1 AND the
        worker's queued-bytes wait loop; all batches must still arrive in
        order (liveness of the budget path)."""
        monkeypatch.setenv("DL4J_TPU_TRANSFER_STAGE_BYTES", "1")
        from deeplearning4j_tpu.datasets.async_iterator import (
            AsyncDataSetIterator)
        from deeplearning4j_tpu.datasets.dataset import (DataSet,
                                                         ListDataSetIterator)
        batches = [DataSet(np.full((8, 4), i, np.float32),
                           np.zeros((8, 2), np.float32)) for i in range(30)]
        it = AsyncDataSetIterator(ListDataSetIterator(batches), stage=8)
        seen = [float(np.asarray(d.features)[0, 0]) for d in it]
        assert seen == [float(i) for i in range(30)]
        # reset and drain again (fresh worker, fresh budget accounting)
        it.reset()
        assert len(list(it)) == 30
        it.shutdown()

    def test_generous_budget_still_stages_groups(self, rng, monkeypatch):
        monkeypatch.setenv("DL4J_TPU_TRANSFER_STAGE_BYTES",
                           str(64 * 1024 * 1024))
        from deeplearning4j_tpu.datasets.async_iterator import (
            AsyncDataSetIterator)
        from deeplearning4j_tpu.datasets.dataset import (DataSet,
                                                         ListDataSetIterator)
        batches = [DataSet(rng.rand(16, 10).astype(np.float32),
                           rng.rand(16, 2).astype(np.float32))
                   for _ in range(12)]
        it = AsyncDataSetIterator(ListDataSetIterator(batches), stage=4)
        assert it._group_target(batches[0]) == 4
        out = list(it)
        assert len(out) == 12
        import jax
        assert all(isinstance(d.features, jax.Array) for d in out)
        it.shutdown()
