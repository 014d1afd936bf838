/*
 * Broker-side shim: implements the KIP-405 RemoteStorageManager SPI by
 * forwarding the five operations to the tieredstorage_tpu sidecar process
 * over its shim-wire HTTP boundary (tieredstorage_tpu/sidecar/shimwire.py,
 * served by `python -m tieredstorage_tpu.sidecar --port N`).
 *
 * Deliberately dependency-free: only the JDK (java.net.http, java.io) and
 * kafka-storage-api (already on every broker's classpath). No grpc-java /
 * protobuf-java / netty shading — a broker operator deploys exactly one
 * small jar. Mirrors the plugin surface of the reference's in-process
 * implementation (core/.../RemoteStorageManager.java:106,143,212,529-541,
 * 594,673,700); here the accelerator runtime lives in the sidecar and this
 * class is only transport + error mapping.
 *
 * Broker configuration:
 *   remote.log.storage.manager.class.name=io.tieredstorage.tpu.shim.SidecarRemoteStorageManager
 *   rsm.config.sidecar.endpoint=http://127.0.0.1:18445
 *   rsm.config.sidecar.request.timeout.ms=30000
 */
package io.tieredstorage.tpu.shim;

import java.io.ByteArrayInputStream;
import java.io.ByteArrayOutputStream;
import java.io.DataOutputStream;
import java.io.IOException;
import java.io.InputStream;
import java.io.SequenceInputStream;
import java.io.UncheckedIOException;
import java.net.URI;
import java.net.http.HttpClient;
import java.net.http.HttpRequest;
import java.net.http.HttpResponse;
import java.nio.ByteBuffer;
import java.nio.charset.StandardCharsets;
import java.nio.file.Files;
import java.nio.file.Path;
import java.time.Duration;
import java.util.ArrayList;
import java.util.List;
import java.util.Map;
import java.util.Objects;
import java.util.Optional;
import java.util.TreeMap;

import org.apache.kafka.common.Uuid;
import org.apache.kafka.server.log.remote.storage.LogSegmentData;
import org.apache.kafka.server.log.remote.storage.RemoteLogSegmentMetadata;
import org.apache.kafka.server.log.remote.storage.RemoteLogSegmentMetadata.CustomMetadata;
import org.apache.kafka.server.log.remote.storage.RemoteResourceNotFoundException;
import org.apache.kafka.server.log.remote.storage.RemoteStorageException;
import org.apache.kafka.server.log.remote.storage.RemoteStorageManager;

public class SidecarRemoteStorageManager implements RemoteStorageManager {

    public static final String SIDECAR_ENDPOINT_CONFIG = "sidecar.endpoint";
    public static final String REQUEST_TIMEOUT_MS_CONFIG = "sidecar.request.timeout.ms";
    private static final long DEFAULT_REQUEST_TIMEOUT_MS = 30_000;
    private static final int WIRE_VERSION = 1;

    private HttpClient client;
    private URI baseUri;
    private Duration requestTimeout;

    @Override
    public void configure(final Map<String, ?> configs) {
        final Object endpoint = configs.get(SIDECAR_ENDPOINT_CONFIG);
        if (endpoint == null) {
            throw new IllegalArgumentException(SIDECAR_ENDPOINT_CONFIG + " must be set");
        }
        this.baseUri = URI.create(endpoint.toString());
        final Object timeout = configs.get(REQUEST_TIMEOUT_MS_CONFIG);
        final long timeoutMs = timeout == null
            ? DEFAULT_REQUEST_TIMEOUT_MS
            : Long.parseLong(timeout.toString());
        this.requestTimeout = Duration.ofMillis(timeoutMs);
        this.client = HttpClient.newBuilder()
            .version(HttpClient.Version.HTTP_1_1)
            .connectTimeout(Duration.ofMillis(Math.min(timeoutMs, 10_000)))
            .build();
    }

    // ------------------------------------------------------------------ SPI

    @Override
    public Optional<CustomMetadata> copyLogSegmentData(
            final RemoteLogSegmentMetadata remoteLogSegmentMetadata,
            final LogSegmentData logSegmentData) throws RemoteStorageException {
        Objects.requireNonNull(remoteLogSegmentMetadata, "remoteLogSegmentMetadata must not be null");
        Objects.requireNonNull(logSegmentData, "logSegmentData must not be null");
        try {
            final HttpResponse<byte[]> response = client.send(
                HttpRequest.newBuilder(resolve("/v1/copy"))
                    .timeout(requestTimeout)
                    .POST(HttpRequest.BodyPublishers.ofInputStream(
                        () -> copyBody(remoteLogSegmentMetadata, logSegmentData)))
                    .build(),
                HttpResponse.BodyHandlers.ofByteArray());
            if (response.statusCode() == 204) {
                return Optional.empty();
            }
            if (response.statusCode() == 200) {
                return Optional.of(new CustomMetadata(response.body()));
            }
            throw mapError(response.statusCode(),
                new String(response.body(), StandardCharsets.UTF_8));
        } catch (final IOException | InterruptedException e) {
            throw transportError("copyLogSegmentData", e);
        }
    }

    @Override
    public InputStream fetchLogSegment(
            final RemoteLogSegmentMetadata remoteLogSegmentMetadata,
            final int startPosition) throws RemoteStorageException {
        return fetchStream("/v1/fetch",
            concat(encodeMetadata(remoteLogSegmentMetadata),
                   encodeFetchTail(startPosition, null)));
    }

    @Override
    public InputStream fetchLogSegment(
            final RemoteLogSegmentMetadata remoteLogSegmentMetadata,
            final int startPosition,
            final int endPosition) throws RemoteStorageException {
        return fetchStream("/v1/fetch",
            concat(encodeMetadata(remoteLogSegmentMetadata),
                   encodeFetchTail(startPosition, (long) endPosition)));
    }

    @Override
    public InputStream fetchIndex(
            final RemoteLogSegmentMetadata remoteLogSegmentMetadata,
            final IndexType indexType) throws RemoteStorageException {
        final byte[] name = indexType.name().getBytes(StandardCharsets.UTF_8);
        final ByteArrayOutputStream tail = new ByteArrayOutputStream();
        final DataOutputStream out = new DataOutputStream(tail);
        try {
            out.writeShort(name.length);
            out.write(name);
        } catch (final IOException e) {
            throw new UncheckedIOException(e); // ByteArrayOutputStream cannot throw
        }
        return fetchStream("/v1/fetch-index",
            concat(encodeMetadata(remoteLogSegmentMetadata), tail.toByteArray()));
    }

    @Override
    public void deleteLogSegmentData(
            final RemoteLogSegmentMetadata remoteLogSegmentMetadata)
            throws RemoteStorageException {
        try {
            final HttpResponse<byte[]> response = client.send(
                HttpRequest.newBuilder(resolve("/v1/delete"))
                    .timeout(requestTimeout)
                    .POST(HttpRequest.BodyPublishers.ofByteArray(
                        encodeMetadata(remoteLogSegmentMetadata)))
                    .build(),
                HttpResponse.BodyHandlers.ofByteArray());
            if (response.statusCode() != 204 && response.statusCode() != 200) {
                throw mapError(response.statusCode(),
                    new String(response.body(), StandardCharsets.UTF_8));
            }
        } catch (final IOException | InterruptedException e) {
            throw transportError("deleteLogSegmentData", e);
        }
    }

    @Override
    public void close() {
        // java.net.http.HttpClient frees its resources with the instance
        // (AutoCloseable only from Java 21; brokers commonly run 11/17).
        // Deliberately do NOT null the field: broker remote-fetch threads
        // can race plugin close(), and an in-flight call must fail with a
        // mapped RemoteStorageException from the transport, never an NPE.
    }

    // ------------------------------------------------------------ transport

    private URI resolve(final String path) {
        return URI.create(baseUri.toString().replaceAll("/$", "") + path);
    }

    private InputStream fetchStream(final String path, final byte[] body)
            throws RemoteStorageException {
        try {
            final HttpResponse<InputStream> response = client.send(
                HttpRequest.newBuilder(resolve(path))
                    .timeout(requestTimeout)
                    .POST(HttpRequest.BodyPublishers.ofByteArray(body))
                    .build(),
                HttpResponse.BodyHandlers.ofInputStream());
            if (response.statusCode() == 200) {
                return response.body();
            }
            final String message;
            try (InputStream err = response.body()) {
                message = new String(err.readAllBytes(), StandardCharsets.UTF_8);
            }
            throw mapError(response.statusCode(), message);
        } catch (final IOException | InterruptedException e) {
            throw transportError(path, e);
        }
    }

    private static RemoteStorageException mapError(final int status, final String message) {
        if (status == 404) {
            return new RemoteResourceNotFoundException(message);
        }
        return new RemoteStorageException("sidecar returned HTTP " + status + ": " + message);
    }

    private static RemoteStorageException transportError(final String op, final Exception e) {
        if (e instanceof InterruptedException) {
            Thread.currentThread().interrupt();
        }
        return new RemoteStorageException("sidecar " + op + " failed: " + e, e);
    }

    // ---------------------------------------------------------- wire format
    // Shim wire v1 (tieredstorage_tpu/sidecar/shimwire.py): big-endian,
    // DataOutputStream-native.

    static byte[] encodeMetadata(final RemoteLogSegmentMetadata md) {
        final ByteArrayOutputStream buf = new ByteArrayOutputStream();
        final DataOutputStream out = new DataOutputStream(buf);
        try {
            out.writeByte(WIRE_VERSION);
            writeUuid(out, md.remoteLogSegmentId().topicIdPartition().topicId());
            writeUuid(out, md.remoteLogSegmentId().id());
            final byte[] topic = md.remoteLogSegmentId().topicIdPartition()
                .topicPartition().topic().getBytes(StandardCharsets.UTF_8);
            out.writeShort(topic.length);
            out.write(topic);
            out.writeInt(md.remoteLogSegmentId().topicIdPartition().topicPartition().partition());
            out.writeLong(md.startOffset());
            out.writeLong(md.endOffset());
            out.writeLong(md.maxTimestampMs());
            out.writeInt(md.brokerId());
            out.writeLong(md.eventTimestampMs());
            final TreeMap<Integer, Long> epochs = new TreeMap<>(md.segmentLeaderEpochs());
            out.writeInt(epochs.size());
            for (final Map.Entry<Integer, Long> e : epochs.entrySet()) {
                out.writeInt(e.getKey());
                out.writeLong(e.getValue());
            }
            out.writeLong(md.segmentSizeInBytes());
            final Optional<CustomMetadata> custom = md.customMetadata();
            if (custom.isPresent()) {
                final byte[] value = custom.get().value();
                out.writeByte(1);
                out.writeInt(value.length);
                out.write(value);
            } else {
                out.writeByte(0);
            }
        } catch (final IOException e) {
            throw new UncheckedIOException(e); // ByteArrayOutputStream cannot throw
        }
        return buf.toByteArray();
    }

    static byte[] encodeFetchTail(final long start, final Long endInclusive) {
        final ByteBuffer buf = ByteBuffer.allocate(8 + 1 + 8);
        buf.putLong(start);
        buf.put((byte) (endInclusive != null ? 1 : 0));
        buf.putLong(endInclusive != null ? endInclusive : 0L);
        return buf.array();
    }

    private static void writeUuid(final DataOutputStream out, final Uuid uuid)
            throws IOException {
        out.writeLong(uuid.getMostSignificantBits());
        out.writeLong(uuid.getLeastSignificantBits());
    }

    private static byte[] concat(final byte[] a, final byte[] b) {
        final byte[] out = new byte[a.length + b.length];
        System.arraycopy(a, 0, out, 0, a.length);
        System.arraycopy(b, 0, out, a.length, b.length);
        return out;
    }

    /** Copy body: metadata block + six framed sections, file contents
     * streamed (not buffered) so multi-GiB segments do not double in heap.
     * Streams opened before a later section fails are closed on the way
     * out — Kafka's RLM retries failed copies, so a leak here would bleed
     * one fd per retry (e.g. a segment file deleted between scheduling and
     * execution). */
    private InputStream copyBody(final RemoteLogSegmentMetadata md,
                                 final LogSegmentData data) {
        final List<InputStream> parts = new ArrayList<>();
        try {
            parts.add(new ByteArrayInputStream(encodeMetadata(md)));
            addFileSection(parts, data.logSegment());
            addFileSection(parts, data.offsetIndex());
            addFileSection(parts, data.timeIndex());
            addFileSection(parts, data.producerSnapshotIndex());
            if (data.transactionIndex().isPresent()) {
                addFileSection(parts, data.transactionIndex().get());
            } else {
                parts.add(new ByteArrayInputStream(new byte[] {0}));
            }
            final ByteBuffer leaderEpoch = data.leaderEpochIndex().duplicate();
            final byte[] epochBytes = new byte[leaderEpoch.remaining()];
            leaderEpoch.get(epochBytes);
            parts.add(new ByteArrayInputStream(sectionHeader(epochBytes.length)));
            parts.add(new ByteArrayInputStream(epochBytes));
            return new SequenceInputStream(java.util.Collections.enumeration(parts));
        } catch (final IOException | RuntimeException e) {
            for (final InputStream opened : parts) {
                try {
                    opened.close();
                } catch (final IOException ignored) {
                    // closing best-effort on the failure path
                }
            }
            if (e instanceof IOException) {
                throw new UncheckedIOException((IOException) e);
            }
            throw (RuntimeException) e;
        }
    }

    private static void addFileSection(final List<InputStream> parts, final Path file)
            throws IOException {
        parts.add(new ByteArrayInputStream(sectionHeader(Files.size(file))));
        parts.add(Files.newInputStream(file));
    }

    private static byte[] sectionHeader(final long length) {
        final ByteBuffer buf = ByteBuffer.allocate(1 + 8);
        buf.put((byte) 1);
        buf.putLong(length);
        return buf.array();
    }
}
